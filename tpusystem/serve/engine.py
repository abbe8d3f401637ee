"""Continuous-batching decode engine: one compiled step, churning rows.

The engine runs a fixed-shape ``[rows, 1]`` token-step under
``jit`` — the ``per_row_decode`` discipline from the speculative path
(:mod:`tpusystem.train.generate`), extended to independent user
sequences over the paged KV cache
(:func:`tpusystem.ops.attention.paged_attention`). Batch membership
changes every step **without retracing**:

* **admit** — the prompt prefills through a plain contiguous decode
  apply (one compiled prefill program per pad bucket —
  :func:`prefill_bucket`), the blocks the prompt fills of the resulting
  KV strip are written whole into free-list blocks
  (:func:`tpusystem.serve.kvcache.adopt_prefill`), and
  the row's block table and cursor are edited host-side. The prefill
  logits' argmax is the request's first token.
* **step** — every row advances one token a dispatch, sent ahead of the
  read of the one before (:meth:`Engine.step`); retired rows idle masked.
* **evict** — blocks return to the free list and the row's table resets
  to trash; the decode program never sees a shape change.

Greedy outputs are **token-exact against standalone**
:func:`tpusystem.train.generate.generate` for every request, regardless
of co-batched traffic, in window-length-invariant arithmetic (f32
modules; masked attention positions contribute exact zeros, so a row
never observes its neighbors — pinned by ``tests/test_serve.py``).

The decode-roofline levers compose on top of that contract:

* ``stream_dtype`` applies :func:`generate`'s weight-streaming levers to
  the engine's param tree ('int8' halves the per-step streamed weight
  bytes vs bf16; dequantization stays inside the compiled step so the
  narrow leaves remain the HBM-resident operand).
* ``decode_impl='fused'`` routes the one jitted step through the Pallas
  fused decode chain
  (:func:`tpusystem.train.decode_fused.build_fused_paged_step` — the
  ``[rows, dim]`` activation VMEM-resident, the fc→gelu→proj pair one
  kernel, int8/fp8 tiles dequantized in-kernel), gated by
  :func:`tpusystem.train.decode_fused.fused_paged_reason` and
  token-exact vs the flax step.
* ``share_prefix=True`` turns on the radix prefix index
  (:class:`tpusystem.serve.kvcache.PagedKVCache`): admissions whose
  prompt starts with an already-cached block-aligned prefix adopt those
  blocks by reference and prefill **only the uncached suffix** (the
  resume prefill seeds a contiguous cache from pool gathers and applies
  the suffix down the decode path — window-invariant, so tokens don't
  move).
* ``draft_module`` switches the step to **speculative rows**: each
  request owns ``tree_fanout`` adjacent branch rows of the same paged
  pool; the draft fans/extends each branch ``speculate`` tokens and ONE
  target forward verifies every branch window, emitting the longest
  target-accepted prefix plus one corrected token per request —
  between 1 and ``speculate + 1`` tokens per step, still exactly the
  target's sequential decode (greedy or seeded-sampled — the verify
  samples each window position at its own ``(seed, position)``
  counter, so acceptance-against-greedy-drafts only changes speed,
  never the stream). Losing branches' blocks never leave the pool
  accounting: block membership is fixed per request; the winner's
  verify window is copied across siblings inside the step.
* ``sampling=`` on admission turns a row sampled: per-request
  :class:`SamplingParams` (seed / temperature / top-k / top-p and the
  grammar ``mask_fn`` hook) live as batched DEVICE arrays the one
  compiled step reads — param churn never retraces (``trace_count``
  stays 1). Every sampled token's threefry key is a pure function of
  ``(seed, position)`` (:func:`tpusystem.train.generate.sampling_key`),
  so the journal's emitted prefix is the ONLY replay state: a replayed,
  rerouted, or hedged row reproduces the identical sample stream
  bitwise on any engine. ``temperature == 0`` (the default) is the
  plain greedy argmax, bitwise-unchanged — and a dispatch none of whose
  rows samples runs that argmax alone
  (:func:`tpusystem.train.generate.select_tokens`: one ``cond`` on the
  device per program call; ``selection`` counts each side's ticks).
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from tpusystem.observe.profile import annotate
from tpusystem.ops.attention import paged_read
from tpusystem.parallel.mesh import on_tpu
from tpusystem.serve.kvcache import (PagedKVCache, _is_kv, adopt_prefill,
                                     pool_shardings, write_tables)
from tpusystem.train.cursors import (gather_rows, holds_row_state, is_cursor,
                                     is_row_state, read_cursor, rewind)
from tpusystem.train.decode_fused import (build_fused_paged_step,
                                          fused_paged_reason)
from tpusystem.train.generate import (_decoder, _dequant, _stream_params,
                                      select_tokens)


class Saturated(RuntimeError):
    """No free row or not enough free blocks — the request must stay
    queued (the scheduler's job), never crash the engine."""


class UnseededSampling(ValueError):
    """A ``temperature > 0`` request with no seed: its stream would be
    non-reproducible, which vacates every replay/reroute/hedging
    guarantee this stack makes — refused typed at the front door
    (router, scheduler, AND engine) instead of silently degrading to a
    divergent duplicate. Subclasses ``ValueError`` so existing
    caller-error handling (trace closed ``'invalid'``, re-raise)
    applies unchanged."""


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode-sampling controls, journal-replayable.

    Rides the request through scheduler, journal, handoff, and hedging:
    a seeded request's token at stream position ``p`` is a pure
    function of ``(seed, p)`` plus the emitted prefix, so replay needs
    no RNG state beyond what the journal already records.

    Attributes:
        seed: threefry counter seed. Required when ``temperature > 0``
            (an unseeded sampled request raises
            :class:`UnseededSampling` at submit); ignored at
            ``temperature == 0``.
        temperature: 0 (default) is greedy argmax — bitwise the
            engine's classic path; > 0 scales logits before sampling.
        top_k: keep only the k highest logits (0 = no top-k filter).
        top_p: nucleus filter — keep the smallest sorted prefix whose
            cumulative mass reaches ``top_p`` (1.0 = no filter).
        mask_fn: the structured-output hook — a picklable
            **module-level** callable ``(emitted: list[int]) ->
            bool[vocab]`` (journal replay re-imports it) evaluated
            host-side before every sampled position; ``False`` tokens
            are excluded before temperature/top-k/top-p. Must allow at
            least one token (an all-False mask is a typed caller
            error — give the grammar an escape hatch such as EOS).
            Composes with greedy too (masked argmax). Does NOT compose
            with speculative rows (the mask cannot update inside a
            multi-token verify window — typed at admit).
    """
    seed: int | None = None
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    mask_fn: object = None

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(
                f'temperature must be >= 0, got {self.temperature}')
        if self.top_k < 0:
            raise ValueError(f'top_k must be >= 0, got {self.top_k}')
        if not 0 < self.top_p <= 1:
            raise ValueError(
                f'top_p must be in (0, 1], got {self.top_p}')

    @property
    def sampled(self) -> bool:
        """True when this request actually samples (``temperature > 0``)."""
        return self.temperature > 0


def engine_unsupported_reason(module) -> str | None:
    """None when the paged engine can serve this module, else why not
    (the ``fused_paged_reason`` capability-gate discipline).

    Served today, all unrolled: the GPT2 and Llama families, including
    **MoE** GPT2 stacks (decode-mode expert dispatch at full capacity:
    :class:`tpusystem.ops.moe.MoEMLP` ``full_capacity``), and the
    **DeepSeek-V2** family — latent attention over a latent paged pool
    (one ``key`` leaf ``[slots, kv_rank + rope]`` a layer and no
    ``value`` leaf: admission, tables and cursors go by leaf name, so
    the pool's row is whatever the module's cache collection declares)
    and :class:`tpusystem.ops.moe.GatedExperts` layers holding a share
    of the router's experts (grouped products over the seated
    assignments: no capacity, no drop, no dependence on co-batched
    rows; their ``expert_load`` counters ride the tick's token read), and
    the **Nemotron-H** family — a pattern string of single-mixer layers
    whose Mamba-2 layers (:class:`tpusystem.ops.ssm.Mamba2`) keep a
    **per-row state cache beside the paged pool**: ``state`` (float32
    ``[rows, H, P, N]``) and ``conv`` (the convolution's last inputs) are
    addressed by row, have no blocks, table or masked positions, and are
    told the prompt's true length at prefill (a recurrence sees its
    right-padding; the engine hands ``length`` to a module whose
    ``__call__`` takes it). A retired row's state needs no clearing:
    admission overwrites the whole row
    (:func:`tpusystem.serve.kvcache.adopt_prefill`), and until then a parked
    row's update reads and writes that row alone, so nothing it holds can
    reach a seated row. What a recurrent state cannot do yet is refused by
    name at construction (:func:`recurrent_reason`): ``share_prefix`` (a
    shared prefix needs a state snapshot at its block boundary), a draft
    module (a rejected token cannot be rolled out of a state) and a mesh
    (no sharding contract for the state). The remaining gate is layout,
    not architecture."""
    for field in ('decode', 'max_seq', 'per_row_decode', 'decode_pages'):
        if not hasattr(module, field):
            return (f'module {type(module).__name__} has no {field!r} '
                    'field — the engine needs the family decode '
                    'conventions (GPT2 / Llama / DeepSeekV2 / NemotronH)')
    if getattr(module, 'scan_layers', False):
        return ('scan_layers stacks the per-layer caches at a leading '
                'layer dim; the engine admission writes are unrolled-'
                'layout only — serve the unrolled module')
    return None


def is_recurrent(module) -> bool:
    """Whether the module's decode cache holds per-row state leaves
    (``state``/``conv``), read from the shapes its decode clone declares."""
    shapes = jax.eval_shape(
        functools.partial(_decoder(module).init, jax.random.PRNGKey(0)),
        jnp.zeros((1, 1), jnp.int32))
    return holds_row_state(shapes.get('cache', {}))


def recurrent_reason(module, *, share_prefix: bool = False,
                     draft_module=None, sharded: bool = False) -> str | None:
    """Why the engine cannot serve this module with these options, where a
    recurrent state (:func:`is_recurrent`) is in the way; else ``None``."""
    wanted = [
        (share_prefix, 'share_prefix=True', 'a shared prefix is adopted by '
         'blocks, and the recurrent state at the block boundary was never '
         'kept: prefix sharing over a recurrent state needs state snapshots '
         'at block boundaries'),
        (draft_module is not None, 'a draft module (speculative rows)',
         'a rejected draft token cannot be rolled back out of a recurrent '
         'state (cursors.rewind refuses it): speculative rows need a '
         'rewindable state'),
        (sharded, 'a mesh', 'the per-row recurrent state has no sharding '
         'contract (pool_shardings shards keys and values by head)')]
    for asked, name, why in wanted:
        if asked and any(is_recurrent(each) for each in (module, draft_module)
                         if each is not None):
            return (f'{name} does not compose with a recurrent state '
                    f'({type(module).__name__}): {why}')
    return None


def prefill_bucket(length: int, block_size: int, max_seq: int) -> int:
    """Pad-to-bucket width for a prompt: the smallest power-of-2 at
    least ``max(length, block_size)``, capped at ``max_seq`` — so a
    stream of varied prompt lengths compiles a **bounded** set of
    prefill programs (the retrace-trap discipline) instead of one per
    length."""
    bucket = max(length, block_size)
    bucket = 1 << (bucket - 1).bit_length()
    return min(bucket, max_seq)


@functools.cache
def _compiled_prefill(decoder, bucket: int, routed: bool = False):
    """One compiled prefill program per (decode clone, pad bucket) —
    ``cache_info()`` is the compile-count witness the bucketing tests
    pin."""
    return _build_prefill(decoder, bucket, routed)


def _build_prefill(decoder, bucket: int, routed: bool = False):
    """``routed``: the program also returns the experts every padded
    position was given, ``[bucket, expert layers, k]`` (an engine with a
    ``routing_sink``)."""
    del bucket          # part of the cache key; shapes key the jit cache
    # a module whose __call__ takes `length` is told how much of the padded
    # prompt is real (an operand: still one program a bucket)
    told = 'length' in inspect.signature(type(decoder).__call__).parameters

    @jax.jit
    def run(params, padded, length, seed, position, temp, topk, topp, mask):
        # plain contiguous prefill: one causal pass over the padded
        # prompt builds every layer's [1, max_seq, ...] KV strip; the
        # right-pad junk is causally invisible to the real positions of an
        # attention layer, and a recurrent layer is told where it starts
        logits, state = decoder.apply(
            {'params': _dequant(params, decoder)}, padded,
            **({'length': length} if told else {}),
            mutable=['cache', 'routing'] if routed else ['cache'])
        # the first token samples at the row's own (seed, position)
        # counter — greedy defaults reproduce the classic argmax bitwise
        first = select_tokens(logits[0, length - 1], seed, position, temp,
                              topk, topp, mask)
        if routed:
            return (first, state['cache'], _routing_of(state),
                    *_selections_of(state))
        return first, state['cache']

    return run


def _in_layer_order(tree) -> dict:
    """``{leaf name: [that leaf of every layer, first layer first]}`` of a
    collection the layers ``sow`` into (``layer_10`` sorts after
    ``layer_9``, not after ``layer_1``)."""
    natural = lambda item: [int(part) if part.isdigit() else part for part
                            in re.split(r'(\d+)',
                                        jax.tree_util.keystr(item[0]))]
    found: dict = {}
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(tree),
                             key=natural):
        found.setdefault(path[-1].key, []).append(leaf)
    return found


def _compact(chosen: np.ndarray) -> np.ndarray:
    """Routing as a ``routing_sink`` gets it: a byte an expert where the
    router is narrow enough (and nothing else in the array is wider)."""
    narrow = chosen.min(initial=0) >= 0 and chosen.max(initial=0) < 256
    return chosen.astype(np.uint8) if narrow else chosen


def _routing_of(state) -> jax.Array:
    """``[tokens, expert layers, k]`` from the ``routing`` collection
    (``ops.moe.GatedExperts``' ``chosen``, ``[tokens, k]`` a layer)."""
    return jnp.stack(_in_layer_order(state['routing'])['chosen'], axis=1)


def _selections_of(state) -> tuple:
    """``([tokens, sparse layers * words],)``: what sparse attention layers
    sow into the ``routing`` collection (``selected``, each position's
    selection as packed words, ``[tokens, words]`` a layer), side by side;
    ``()`` where no layer sows one."""
    found = _in_layer_order(state['routing']).get('selected')
    return (jnp.concatenate(found, axis=1),) if found else ()


def _prompt_routing(routing: list, size: int, selections: bool):
    """The prompt's routing from the prefill program's outputs, as a
    ``routing_sink`` gets it: the experts, and where ``selections`` the
    sparse attention layers' selections behind them, flattened."""
    experts = np.asarray(routing[0])[:size]
    if not selections:
        return _compact(experts)
    return np.concatenate([experts.reshape(size, -1),
                           np.asarray(routing[1])[:size]], axis=1)


@functools.cache
def _compiled_resume(decoder, bucket: int):
    return _build_resume(decoder, bucket)


def _build_resume(decoder, bucket: int):
    """The shared-prefix **resume prefill**: seed a contiguous decode
    cache with the row's already-cached prefix KV (gathered from the
    paged pool through the row's slot map) and its cursors at the cached
    depth, then apply only the padded SUFFIX — ``cached_attention``
    takes its decode path (the cache variables pre-exist), whose
    bucketed masked read equals the full causal prefill in
    window-length-invariant arithmetic, so the suffix logits — and the
    request's first token — are exactly the full prefill's. One program
    per suffix pad bucket. (Caveat, documented in docs/serving.md:
    prompts whose FULL prefill would route the flash kernel — length >=
    512 — mix flash-era prefix KV with the einsum decode read, exact
    only up to the platform's near-tie argmax tolerance.)"""
    del bucket          # part of the cache key; shapes key the jit cache
    shapes = jax.eval_shape(
        functools.partial(decoder.init, jax.random.PRNGKey(0)),
        jnp.zeros((1, 1), jnp.int32))['cache']

    @jax.jit
    def run(params, cache, slots, padded, cached_len, suffix_len,
            seed, position, temp, topk, topp, mask):
        source = {jax.tree_util.keystr(path): leaf for path, leaf
                  in jax.tree_util.tree_leaves_with_path(cache)}
        keep = jnp.arange(decoder.max_seq) < cached_len

        def seed_leaf(path, leaf):
            if _is_kv(path):
                pool = source[jax.tree_util.keystr(path)]
                strip = jnp.take(pool, slots, axis=0)    # [max_seq, h*d]
                strip = jnp.where(keep[:, None], strip, 0)
                return strip.reshape(leaf.shape).astype(leaf.dtype)
            if is_cursor(path):
                return jnp.full(leaf.shape, cached_len, leaf.dtype)
            return jnp.zeros(leaf.shape, leaf.dtype)

        resumed = jax.tree_util.tree_map_with_path(seed_leaf, shapes)
        logits, state = decoder.apply(
            {'params': _dequant(params, decoder), 'cache': resumed},
            padded, mutable=['cache'])
        first = select_tokens(logits[0, suffix_len - 1], seed, position,
                              temp, topk, topp, mask)
        return first, state['cache']

    return run


@functools.partial(jax.jit, donate_argnums=(0,))
def _adopt_draft_rows(dcache, prefill_cache, rows, length):
    """Seat a draft prefill strip in ``rows`` of the contiguous per-row
    draft cache (every branch row of one speculative group gets the same
    prompt KV): KV leaves overwrite whole row strips, cursor leaves set
    to the prompt length. Fixed shapes — one compiled program."""
    source = {jax.tree_util.keystr(path): leaf for path, leaf
              in jax.tree_util.tree_leaves_with_path(prefill_cache)}

    def fix(path, leaf):
        if _is_kv(path):
            strip = source[jax.tree_util.keystr(path)]   # [1, S, h, d]
            wide = jnp.broadcast_to(strip,
                                    (rows.shape[0],) + strip.shape[1:])
            return leaf.at[rows].set(wide.astype(leaf.dtype))
        if is_cursor(path):
            return leaf.at[rows].set(jnp.asarray(length, leaf.dtype))
        return leaf
    return jax.tree_util.tree_map_with_path(fix, dcache)


def _copy_winner_windows(cache, win_rows_wide, cursor, speculate: int,
                         block: int, max_blocks: int):
    """Token-tree verify's winner-copy, paged-pool flavored: every
    branch row's verify window (positions ``cursor .. cursor +
    speculate``, all past the shared prompt region) is overwritten from
    its group winner's window — a pool gather + scatter through each
    row's OWN block table, so losers' private decode blocks inherit the
    winning branch's KV and block membership never changes (no free-list
    traffic inside the step). Past-allocation positions map to trash on
    both sides (dead copies)."""
    positions = cursor[:, None] + jnp.arange(speculate + 1)[None, :]
    logical = jnp.minimum(positions // block, max_blocks - 1)

    def walk(node):
        if isinstance(node, dict) and 'table' in node and 'key' in node:
            table = node['table']
            dst_phys = jnp.take_along_axis(table, logical, axis=1)
            src_phys = jnp.take_along_axis(
                jnp.take(table, win_rows_wide, axis=0), logical, axis=1)
            dst = (dst_phys * block + positions % block).reshape(-1)
            src = (src_phys * block + positions % block).reshape(-1)
            out = dict(node)
            for name in ('key', 'value'):
                if name not in node:     # a latent pool has one KV leaf
                    continue
                pool = node[name]
                out[name] = pool.at[dst].set(jnp.take(pool, src, axis=0))
            return out
        if isinstance(node, dict):
            return {name: walk(child) for name, child in node.items()}
        return node
    return walk(cache)


def _greedy_operands(vocab: int) -> tuple:
    """The greedy-default sampling operands ``(seed, position, temperature,
    top_k, top_p, mask)`` on the device: what an unsampled prefill passes
    so its first-token choice is bitwise the classic argmax. An engine
    builds them once; no admission makes a ``[vocab]`` mask of its own."""
    return (jnp.uint32(0), jnp.int32(0), jnp.float32(0.0), jnp.int32(0),
            jnp.float32(1.0), jnp.ones(vocab, bool))


@dataclasses.dataclass
class Admission:
    """What :meth:`Engine.admit` hands back: the row the request landed
    in, its first token (from the prefill logits), and whether that
    token already completed it (``max_new == 1`` or a stop hit)."""
    row: int
    token: int
    finished: bool
    reason: str | None = None       # 'length' | 'stop' when finished


@dataclasses.dataclass
class StepReport:
    """One engine step: ``emitted`` maps row -> the LIST of new tokens
    for every row that was active (one token on the plain step; up to
    ``speculate + 1`` on a speculative step), ``finished`` lists the
    rows retired this step — ``(row, reason, tokens)`` triples, already
    evicted by the time the report returns (the tokens ride out with the
    report because eviction frees the row's state)."""
    emitted: dict
    finished: list                   # [(row, reason, tokens), ...]
    expert_load: dict | None = None  # the tick's Engine.last_expert_load


@dataclasses.dataclass
class _RowState:
    tokens: list
    max_new: int
    stop: int | None
    tag: object = None               # opaque caller handle (request id)
    sampling: object = None          # SamplingParams | None (greedy)
    prior: tuple = ()                # tokens emitted in a previous life
    #                                  (replay prefix) — position and
    #                                  mask_fn both see prior + tokens
    prompt: object = None            # kept only for a routing_sink, with
    routing: list | None = None      # [positions, expert layers, k] pieces

    @property
    def sampled(self) -> bool:
        return self.sampling is not None and self.sampling.sampled


class Engine:
    """The continuous-batching engine over one model's param tree.

    Args:
        module: a family LM module (GPT2 / Llama conventions, MoE
            included; see :func:`engine_unsupported_reason` for the
            scope gate).
        params: trained parameters.
        rows: fixed decode batch width — the compiled step's shape.
        block_size: tokens per KV block.
        blocks: physical blocks in the pool (including the reserved
            trash block 0). Default sizes the pool to back every row at
            full ``max_seq`` depth; smaller pools oversubscribe capacity
            and rely on the scheduler to queue.
        stream_dtype: :func:`tpusystem.train.generate.generate`'s
            weight-streaming lever, applied to the engine's param tree
            ('int8' for the serving default on HBM-bound chips).
        decode_impl: ``'flax'`` | ``'fused'`` | ``'auto'`` — the step
            implementation. ``'fused'`` is the Pallas fused paged step
            (module docstring; raises where
            :func:`tpusystem.train.decode_fused.fused_paged_reason`
            names a gate); ``'auto'`` picks fused on TPU-class backends
            when supported, flax otherwise.
        share_prefix: enable the radix prefix index — co-batched (and
            successive) requests sharing a prompt prefix share KV blocks
            and prefill only their uncached suffix.
        draft_module / draft_params: a cheap draft LM switches the step
            to speculative rows (module docstring) — the output stays
            exactly the target's sequential decode, greedy and
            seeded-sampled alike (``mask_fn`` does not compose;
            a grammar mask cannot update inside a multi-token verify
            window). ``decode_impl='fused'`` does not compose (the
            verify forward is the flax paged step).
        speculate: draft tokens proposed per speculative step.
        tree_fanout: branch rows per request (token-tree verify);
            ``rows`` must be a multiple.
        mesh: a :class:`~tpusystem.parallel.mesh.MeshSpec` or built
            :class:`jax.sharding.Mesh` to TP-shard the compiled steps
            over — params placed by the module's ``partition_rules()``,
            the paged KV pool sharded over heads
            (:func:`~tpusystem.serve.kvcache.pool_shardings`), block
            tables replicated so the host pool stays the one authority.
            Only the ``model`` axis may exceed 1
            (:func:`~tpusystem.parallel.schedule.decode_tp_plan` is the
            gate); ``decode_impl='fused'`` raises under TP (no ring arms
            yet — ``'auto'`` serves the sharded flax step, token-exact
            vs single-device).
        schedule: an :class:`~tpusystem.parallel.schedule.OverlapSchedule`
            threaded onto the decode/prefill clones — per-shape
            ``schedule_applicable`` gating decides whether any program
            takes the manual shard_map path (decode's ``[rows, 1]``
            shapes typically fall back to GSPMD; prefill buckets may
            qualify).
        routing_sink: ``routing_sink(tag, prompt, tokens, routing)`` is
            called when a row retires, with the experts its expert
            layers (``ops.moe.GatedExperts``) gave every position that
            went through them: ``routing [len(prompt) + len(tokens) - 1,
            expert layers, k]`` uint8 or int32, the prompt's positions
            from the prefill program and each decoded token's input from
            the tick's one read. What a caller needs to replay the
            model with the same experts (a reference forward pass, a
            trainer's routing replay). Plain admission only: it does not
            compose with ``share_prefix`` (a shared prefix went through
            the experts in another request's life), speculative rows, a
            mesh, or admission of a prefill made elsewhere.
        routing_selections: with a ``routing_sink`` over a module whose
            sparse attention layers sow their selections
            (``ops.attention.sparse_latent_attention``), hand those out
            too: ``routing [positions, expert layers * k + sparse layers *
            max_seq / 32]`` int32, the choices flattened, then each
            layer's selection as packed words. The programs make the
            selections either way and are the same programs; only this
            reads them off the device (kilobytes a row every tick,
            megabytes a prompt), so a caller that replays a few requests
            asks for them there and not in the traffic it times.

    The decode step traces exactly once per engine (``trace_count`` is
    the witness); admissions and evictions are host-side table edits
    plus fixed-shape device writes, the per-row state among them in one
    compiled program each (a watching :class:`~tpusystem.observe.Tracer`'s
    ``compile.trace`` spans of ``seat`` and ``clear`` are their witness).
    """

    def __init__(self, module, params, *, rows: int = 4,
                 block_size: int = 16, blocks: int | None = None,
                 stream_dtype: str = 'auto', decode_impl: str = 'auto',
                 share_prefix: bool = False, draft_module=None,
                 draft_params=None, speculate: int = 4,
                 tree_fanout: int = 1, mesh=None, schedule=None,
                 routing_sink=None, routing_selections: bool = False,
                 bucket_floor: int = 0) -> None:
        reason = engine_unsupported_reason(module)
        if reason is not None:
            raise ValueError(f'the serving engine cannot run this module: '
                             f'{reason}')
        self.rows, self.block_size = rows, block_size
        self.max_seq, self.bucket_floor = module.max_seq, bucket_floor
        if blocks is None:
            blocks = rows * (self.max_seq // block_size) + 1
        self.stream_dtype = stream_dtype
        self.share_prefix = share_prefix
        self.speculate, self.tree_fanout = speculate, tree_fanout
        self._spec = draft_module is not None
        self.mesh, self.tp_plan = self._resolve_mesh(mesh)
        reason = recurrent_reason(
            module, share_prefix=share_prefix, draft_module=draft_module,
            sharded=self.tp_plan.path == 'gspmd')
        reason = reason or window_reason(
            module, share_prefix=share_prefix, draft_module=draft_module,
            sharded=self.tp_plan.path == 'gspmd')
        if reason is not None:
            raise ValueError(f'the serving engine cannot run this module '
                             f'so: {reason}')
        if self._spec and self.tp_plan.path == 'gspmd':
            raise ValueError(
                'mesh= does not compose with speculative rows yet — the '
                'draft cache has no sharding contract; serve the plain '
                'engine under TP')
        if self._spec:
            if speculate < 1:
                raise ValueError(f'speculate must be >= 1, got {speculate}')
            if tree_fanout < 1:
                raise ValueError(
                    f'tree_fanout must be >= 1, got {tree_fanout}')
            if tree_fanout > draft_module.vocab_size:
                raise ValueError(f'tree_fanout ({tree_fanout}) exceeds the '
                                 f'draft vocab ({draft_module.vocab_size})')
            if rows % tree_fanout:
                raise ValueError(f'rows ({rows}) must be a multiple of '
                                 f'tree_fanout ({tree_fanout}) — each '
                                 'request owns fanout adjacent branch rows')
        self._prefiller = _decoder(module)     # contiguous, shared-cursor
        self._decoder = dataclasses.replace(
            _decoder(module, per_row=True),
            decode_pages=(blocks, block_size))
        if self.tp_plan.path == 'gspmd':
            # re-attach what _decoder deliberately dropped: the live mesh
            # (unhashable — the compile caches' TypeError fallback absorbs
            # it) and the overlap schedule, on BOTH clones so prefill and
            # decode shard identically
            self._prefiller = dataclasses.replace(
                self._prefiller, mesh=self.mesh, schedule=schedule)
            self._decoder = dataclasses.replace(
                self._decoder, mesh=self.mesh, schedule=schedule)
        self._params = _stream_params(self._decoder, params, stream_dtype)
        if self.tp_plan.path == 'gspmd':
            from tpusystem.parallel.sharding import TensorParallel
            self._params = TensorParallel(module.partition_rules()).place(
                self._params, self.mesh)
        self.decode_impl = self._resolve_decode_impl(decode_impl)
        self.pool = PagedKVCache(rows, blocks, block_size, self.max_seq,
                                 share_prefix=share_prefix)
        collections = jax.eval_shape(
            functools.partial(self._decoder.init, jax.random.PRNGKey(0)),
            jnp.zeros((rows, 1), jnp.int32))
        self._cache = jax.tree.map(
            lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
            collections['cache'])
        # how the cache divides between the two kinds: the paged pool's keys
        # and values, and the recurrent layers' per-row state leaves (the
        # scheduler marks it once in its tracer: `cache_bytes`)
        self.cache_bytes = {'kv': 0, 'state': 0}
        for path, leaf in jax.tree_util.tree_leaves_with_path(self._cache):
            kind = _cache_kind(path)
            if kind is not None:
                self.cache_bytes[kind] = (self.cache_bytes.get(kind, 0)
                                          + leaf.size * leaf.dtype.itemsize)
        self.paged_read = self._resolve_paged_read()
        self.sparse_attention = sparse_attention_of(self._decoder,
                                                    self._cache)
        # what the module's layers sow for the host beside the tokens: the
        # names of their expert_load counters, and, for a routing_sink, the
        # [expert layers, k] of the experts a token was given
        self._load_names = tuple(sorted(_in_layer_order(
            collections.get('expert_load', {}))))
        self._routing_sink, self._routed = routing_sink, None
        self._selections = False
        if routing_sink is not None:
            chosen = _in_layer_order(
                collections.get('routing', {})).get('chosen')
            if (not chosen or share_prefix or self._spec
                    or self.tp_plan.path == 'gspmd'
                    or self.decode_impl != 'flax'):
                raise ValueError(
                    'routing_sink needs a module whose expert layers sow '
                    'their choices (ops.moe.GatedExperts), on the plain '
                    'flax step: no share_prefix, draft or mesh')
            self._routed = (len(chosen), chosen[0].shape[-1])
            self._selections = routing_selections
        if routing_selections and not (self._routed and _in_layer_order(
                collections.get('routing', {})).get('selected')):
            raise ValueError(
                'routing_selections needs a routing_sink and a module whose '
                'sparse attention layers sow their selections')
        if self.tp_plan.path == 'gspmd':
            self._cache = jax.device_put(
                self._cache, pool_shardings(
                    self._cache, self.mesh,
                    getattr(module, 'kv_heads', module.heads)))
        # free seats: representative rows — every row when linear, the
        # first row of each fanout-wide adjacent group when speculative
        stride = self.tree_fanout if self._spec else 1
        self._free_rows = list(range(rows - stride, -1, -stride))
        # host mirrors for bookkeeping; the device copies are what the
        # step consumes (tokens feed back device-to-device — the per-
        # step host round trip is ONLY the emitted-token read)
        self._tokens = np.zeros(rows, np.int32)
        self._active = np.zeros(rows, bool)
        self._tokens_dev = jnp.zeros(rows, jnp.int32)
        self._active_dev = jnp.zeros(rows, bool)
        # per-row sampling params as batched device arrays: the one
        # compiled step reads them; a membership change rewrites them in
        # one compiled program (seat at admission, clear at eviction —
        # _build_membership), so param churn retraces neither. Greedy
        # defaults (temp 0, no filters, all-True mask) make an idle or
        # unsampled row bitwise the classic argmax path.
        self.vocab = module.vocab_size
        self._seed_dev = jnp.zeros(rows, jnp.uint32)
        self._pos_dev = jnp.zeros(rows, jnp.int32)
        self._temp_dev = jnp.zeros(rows, jnp.float32)
        self._topk_dev = jnp.zeros(rows, jnp.int32)
        self._topp_dev = jnp.ones(rows, jnp.float32)
        self._mask_dev = jnp.ones((rows, self.vocab), bool)
        everywhere = None
        if self.tp_plan.path == 'gspmd':
            from jax.sharding import NamedSharding, PartitionSpec
            everywhere = NamedSharding(self.mesh, PartitionSpec())
            for name in ('_tokens_dev', '_active_dev', '_seed_dev',
                         '_pos_dev', '_temp_dev', '_topk_dev', '_topp_dev',
                         '_mask_dev'):
                setattr(self, name,
                        jax.device_put(getattr(self, name), everywhere))
        self._seat_rows, self._clear_rows = self._build_membership(
            everywhere)
        # the operands of an unsampled prefill, by vocabulary (the draft's
        # joins below when it differs)
        self._greedy = {self.vocab: _greedy_operands(self.vocab)}
        self._rowstate: dict[int, _RowState] = {}
        self._prefills: dict[object, object] = {}  # unhashable-module path
        self._resumes: dict[int, object] = {}
        self.trace_count = 0
        self.timings = {'prefill': 0.0, 'admit': 0.0, 'step': 0.0}
        # prefix-sharing effectiveness counters (chipbench's serving
        # driver reads these)
        self.sharing = {'admissions': 0, 'prefix_hits': 0,
                        'prompt_tokens': 0, 'shared_tokens': 0,
                        'resumed_prefills': 0}
        # which side of select_tokens' cond each decode dispatch took,
        # counted on the host from the seated rows' SamplingParams (the
        # device decides from the same temperatures, never read back)
        self.selection = {'greedy_ticks': 0, 'sampled_ticks': 0}
        # what the expert layers of a module that counts its load
        # (ops.moe.GatedExperts) were given, read with each tick's tokens
        # under the names the layers sow them by, each summed over the
        # layers: running sums over the ticks, and the last tick's own
        # (None until a tick has counted; a module with no such layer
        # never does)
        self.expert_load = {'ticks': 0,
                            **{name: 0 for name in self._load_names}}
        self.last_expert_load = None
        # seated requests decoding with temperature > 0, kept at register
        # and evict (the observability plane's sampled-traffic gauge)
        self.sampled_rows = 0
        # wall seconds of the most recent decode dispatch (admission and
        # prefill excluded) — the decode-only probe for a custom serving
        # loop that wants to feed failover.StepWatchdog.observe the step
        # alone (ServingReplica's built-in watchdog watches the whole
        # tick on its injectable clock instead)
        self.last_step_seconds = 0.0

        if self._spec:
            self._drafter = _decoder(draft_module, per_row=True)
            self._draft_prefiller = _decoder(draft_module)
            self._dparams = _stream_params(self._drafter, draft_params,
                                           stream_dtype)
            if draft_module.vocab_size not in self._greedy:
                self._greedy[draft_module.vocab_size] = _greedy_operands(
                    draft_module.vocab_size)
            dshapes = jax.eval_shape(
                functools.partial(self._drafter.init,
                                  jax.random.PRNGKey(0)),
                jnp.zeros((rows, 1), jnp.int32))['cache']
            self._dcache = jax.tree.map(
                lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), dshapes)
            self._spec_step = jax.jit(self._build_spec_step(),
                                      donate_argnums=(2, 3))
            self._step = None
            return

        # every row samples at its own (seed, position) counter; a tick
        # with no sampled row takes select_tokens' argmax side alone
        if self.decode_impl == 'fused':
            fused = build_fused_paged_step(self._decoder)

            def step_fn(params, cache, tokens, active, seed, pos, temp,
                        topk, topp, mask):
                self.trace_count += 1        # runs at trace time only
                logits, updated = fused(params, cache, tokens)
                with jax.named_scope('select'):
                    token = select_tokens(logits, seed, pos, temp, topk,
                                          topp, mask)
                cursor = read_cursor(cache)
                return (token,
                        rewind(updated, jnp.where(active, cursor + 1, 0),
                               state_stands=True),
                        jnp.where(active, pos + 1, pos))
        else:
            def step_fn(params, cache, tokens, active, seed, pos, temp,
                        topk, topp, mask):
                self.trace_count += 1        # runs at trace time only
                logits, updated = self._decoder.apply(
                    {'params': _dequant(params, self._decoder),
                     'cache': cache},
                    tokens[:, None],
                    mutable=['cache', 'expert_load']
                    + (['routing'] if self._routed else []))
                with jax.named_scope('select'):
                    token = select_tokens(logits[:, -1], seed, pos, temp,
                                          topk, topp, mask)
                # park retired rows' cursors at 0 so their dead writes
                # stay in the trash block's first slots instead of
                # walking off the table; active rows keep the cursor
                # cached_attention advanced (a recurrent layer's state
                # stands where its row's cursor is put: each took in the
                # one token, and a parked row's is never read again)
                cursor = read_cursor(cache)
                out = (token,
                       rewind(updated['cache'],
                              jnp.where(active, cursor + 1, 0),
                              state_stands=True),
                       jnp.where(active, pos + 1, pos))
                # expert layers that count their load (GatedExperts) hand
                # the counts, and for a routing_sink their choices, to the
                # host behind the tokens, in the one array the tick reads
                # anyway
                loads = _in_layer_order(updated.get('expert_load', {}))
                behind = [jnp.stack([sum(loads[name])
                                     for name in self._load_names])] \
                    if self._load_names else []
                if self._routed:
                    behind.append(_routing_of(updated).reshape(-1))
                if behind:
                    out += (jnp.concatenate([token, *behind]),)
                if self._routed:             # made either way, read if asked
                    out += _selections_of(updated)
                return out

        self._step = jax.jit(step_fn, donate_argnums=(1,))

    @staticmethod
    def _resolve_mesh(mesh):
        """Build a MeshSpec, pass a live Mesh through, and gate via
        :func:`~tpusystem.parallel.schedule.decode_tp_plan` — the typed
        'unsupported' plan (any non-``model`` axis > 1) raises here, so
        an engine that constructs is an engine whose sharding works."""
        from tpusystem.parallel.schedule import decode_tp_plan
        if mesh is not None and hasattr(mesh, 'build'):
            mesh = mesh.build()
        plan = decode_tp_plan(mesh)
        if plan.path == 'unsupported':
            raise ValueError(
                f'the serving engine cannot shard over this mesh: '
                f'{plan.reason}')
        return (mesh if plan.path == 'gspmd' else None), plan

    def _resolve_decode_impl(self, decode_impl: str) -> str:
        if decode_impl not in ('auto', 'flax', 'fused'):
            raise ValueError(f"decode_impl must be 'auto', 'flax' or "
                             f"'fused', got {decode_impl!r}")
        if decode_impl == 'flax':
            return 'flax'
        reason = fused_paged_reason(self._decoder)
        if decode_impl == 'fused':
            if self._spec:
                raise ValueError(
                    "decode_impl='fused' does not compose with "
                    'speculative rows — the tree-verify forward is the '
                    'flax paged step (fused composes with share_prefix '
                    'and int8/fp8 streaming)')
            if reason is not None:
                raise ValueError(f"decode_impl='fused' unsupported: "
                                 f'{reason}')
            return 'fused'
        if self._spec or reason is not None:
            return 'flax'
        return 'fused' if on_tpu() else 'flax'

    def _resolve_paged_read(self) -> dict:
        """Which read the decode step's attention layers take over the
        key/value pool: ``{'read': 'kernel' | 'gather', 'reason': why a
        gather}``, answered at construction from the cache's shapes
        (:func:`tpusystem.ops.attention.paged_read`, the decision
        ``paged_attention`` itself dispatches on; the fused step always
        reads through the kernel). ``read`` is ``None`` over a latent
        pool, whose read ``latent_attention`` chooses. The scheduler
        marks it once in its tracer: ``paged_read``."""
        if self.decode_impl == 'fused':
            return {'read': 'kernel', 'reason': None}
        pools = {path[-1].key: leaf for path, leaf
                 in jax.tree_util.tree_leaves_with_path(self._cache)
                 if _is_kv(path)}
        if 'value' not in pools:
            return {'read': None, 'reason': 'a latent pool holds no keys '
                    'and values a head: latent_attention chooses its read'}
        heads = self._decoder.heads
        kv_heads = getattr(self._decoder, 'kv_heads', heads)
        read, reason = paged_read(
            self.speculate + 1 if self._spec else 1, heads, kv_heads,
            pools['key'].shape[1] // kv_heads, self.block_size,
            self.max_seq // self.block_size, pools['key'].dtype,
            sharded=self.tp_plan.path == 'gspmd')
        return {'read': read, 'reason': reason}

    # ----------------------------------------------------------- membership

    def _build_membership(self, placed):
        """The two programs a membership change runs on the per-row
        arrays. ``seat`` writes an admission's token, active flag, seed,
        stream position, temperature, top-k and top-p into its adjacent
        rows (one, or ``tree_fanout`` when speculative); ``clear`` returns
        an evicted group to the idle greedy default: inactive,
        temperature 0, all-True mask (a row that was neither sampled nor
        masked already holds both; stale seed, position, top-k and top-p
        are inert under temperature 0). The arrays are donated and come
        back under ``placed`` (the TP engine's replicated sharding, or
        None), so the decode step sees the operands it was traced on. The
        request's values arrive as host-typed numpy, ``uint32 [5]`` (row,
        token, seed, position, top-k) and ``float32 [2]`` (temperature,
        top-p), so each program traces once per engine whatever is
        admitted."""
        fanout = self.tree_fanout if self._spec else 1

        def put(array, row, value):
            block = jnp.full((fanout,) + array.shape[1:], value, array.dtype)
            return jax.lax.dynamic_update_slice(
                array, block, (row,) + (0,) * (array.ndim - 1))

        def seat(tokens, active, seed, pos, temp, topk, topp, ints, floats):
            row, token, _, position, top_k = ints.astype(jnp.int32)
            return (put(tokens, row, token), put(active, row, True),
                    put(seed, row, ints[2]), put(pos, row, position),
                    put(temp, row, floats[0]), put(topk, row, top_k),
                    put(topp, row, floats[1]))

        def clear(active, temp, mask, row):
            return (put(active, row, False), put(temp, row, 0.0),
                    put(mask, row, True))

        where = {} if placed is None else {'out_shardings': placed}
        return (jax.jit(seat, donate_argnums=tuple(range(7)), **where),
                jax.jit(clear, donate_argnums=(0, 1, 2), **where))

    # ---------------------------------------------------------- speculative

    def _build_spec_step(self):
        """The speculative-rows step: K+1 fanning draft steps on the
        contiguous per-row draft cache, ONE flax paged verify forward
        over every branch's ``[K+1]`` window, winner selection per
        adjacent fanout group, in-pool winner-window copy, and both
        caches rewound to the accepted depth. Emits ``[groups, K+1]``
        tokens (accepted prefix + correction, zero-padded) plus the
        per-group acceptance count."""
        decoder, drafter = self._decoder, self._drafter
        K, F = self.speculate, self.tree_fanout
        rows, groups = self.rows, self.rows // self.tree_fanout
        block = self.block_size
        max_blocks = self.max_seq // block
        branch = jnp.arange(rows) % F

        def spec_step(params, dparams, cache, dcache, tokens, active,
                      seed, pos, temp, topk, topp, mask):
            self.trace_count += 1            # runs at trace time only
            cursor0 = read_cursor(cache)

            def draft_step(state, step_index):
                dc, tok = state
                logits, updated = drafter.apply(
                    {'params': _dequant(dparams, drafter), 'cache': dc},
                    tok[:, None], mutable=['cache'])
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

            # K+1 draft steps (not K): a fully accepted winner's draft
            # cache must already hold d_K's KV for the next round
            (dcache, _), drafts = jax.lax.scan(
                draft_step, (dcache, tokens), jnp.arange(K + 1))
            drafts = jnp.moveaxis(drafts, 0, 1)[:, :K]   # [rows, K]

            # one target forward verifies every branch of every request
            window = jnp.concatenate([tokens[:, None], drafts], axis=1)
            vlogits, tupdated = decoder.apply(
                {'params': _dequant(params, decoder), 'cache': cache},
                window, mutable=['cache'])

            # verify SAMPLES each window slot j at its own counter
            # (seed, pos + j): the accepted prefix + correction is then
            # exactly the sequential sampled stream — a greedy draft
            # token is accepted iff it equals the sampled target choice,
            # so mismatched drafts cost speed, never the stream. Greedy
            # rows (temp 0) reduce to the classic argmax verify bitwise,
            # and a tick of greedy rows alone runs that argmax and no more.
            with jax.named_scope('select'):
                candidates = select_tokens(
                    vlogits, seed, pos[:, None] + jnp.arange(K + 1), temp,
                    topk, topp, mask)
            matches = (drafts == candidates[:, :K]).astype(jnp.int32)
            accepted = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)

            # the longest accepted prefix wins its group; argmax ties
            # resolve to the lowest branch id = the draft's most
            # probable branch
            per_group = accepted.reshape(groups, F)
            winner = jnp.argmax(per_group, axis=1).astype(jnp.int32)
            accepted_w = jnp.max(per_group, axis=1)      # [G]
            win_rows = jnp.arange(groups) * F + winner
            drafts_w = jnp.take(drafts, win_rows, axis=0)
            correction = jnp.take_along_axis(
                jnp.take(candidates, win_rows, axis=0),
                accepted_w[:, None], axis=1)[:, 0]
            positions = jnp.arange(K + 1)[None, :]
            emitted = jnp.where(
                positions < accepted_w[:, None],
                jnp.pad(drafts_w, ((0, 0), (0, 1))),
                jnp.where(positions == accepted_w[:, None],
                          correction[:, None], 0))       # [G, K+1]
            next_token = jnp.take_along_axis(
                emitted, accepted_w[:, None], axis=1)[:, 0]

            advance = jnp.where(active[::F], accepted_w + 1, 0)
            new_cursor = jnp.where(active,
                                   cursor0 + jnp.repeat(advance, F), 0)
            tcache = tupdated['cache']
            rowmap = jnp.repeat(win_rows, F)
            if F > 1:
                # losing branches inherit the winner's verify window
                # through their OWN tables (private decode blocks; block
                # membership is fixed — no in-step free-list traffic)
                tcache = _copy_winner_windows(tcache, rowmap, cursor0, K,
                                              block, max_blocks)
            tcache = rewind(tcache, new_cursor)
            dcache = rewind(gather_rows(dcache, rowmap), new_cursor)
            wide_next = jnp.repeat(next_token, F)
            new_tokens = jnp.where(active, wide_next, tokens)
            new_pos = jnp.where(active, pos + jnp.repeat(advance, F), pos)
            return emitted, accepted_w, new_tokens, tcache, dcache, new_pos

        return spec_step

    # ------------------------------------------------------------ admission

    @property
    def free_rows(self) -> int:
        return len(self._free_rows)

    @property
    def active_rows(self) -> int:
        return int(self._active.sum())

    def can_admit(self, prompt_len: int, max_new: int,
                  prompt=None) -> bool:
        """Whether an admission of this shape would seat right now.
        Pass the prompt tokens to account for prefix sharing (matched
        blocks don't need allocating); without them the estimate is
        conservative. Optimism is safe either way — :meth:`admit` rolls
        a mid-flight shortfall back into :class:`Saturated`."""
        if not self._free_rows:
            return False
        tokens = prompt_len + max_new
        needed = self.pool.blocks_for(tokens)
        if needed > self.pool.max_blocks:
            return False
        fanout = self.tree_fanout if self._spec else 1
        if self.share_prefix and prompt is not None:
            matched = (self.pool.adoptable_prefix(prompt)[0]
                       // self.block_size)
            # later branches also match the blocks the first branch
            # registers (every fully-prompt-covered block)
            sibling = max(matched, (prompt_len - 1) // self.block_size)
            total = (needed - matched) + (fanout - 1) * (needed - sibling)
        else:
            total = fanout * needed
        return total <= self.pool.free_blocks

    def bucket(self, prompt_len: int) -> int:
        """The prefill program's width for a prompt: the smallest power of
        two at or above the largest of its length, the block size and
        ``bucket_floor`` (an ``Engine`` argument, 0 by default), at most
        ``max_seq``. A floor
        pads short prompts to one program where the prefill costs its
        weight stream whatever the length: fewer compiled programs, and
        one admission cost for every prompt under the floor."""
        return prefill_bucket(max(prompt_len, self.bucket_floor),
                              self.block_size, self.max_seq)

    def prefix_cached_len(self, prompt) -> int:
        """How many leading prompt tokens the radix index would serve
        from cache if this prompt were admitted now (0 without
        sharing) — the scheduler's suffix-budget and the router's
        prefix-affinity probe."""
        if not self.share_prefix:
            return 0
        return self.pool.adoptable_prefix(prompt)[0]

    def admit_cost(self, prompt) -> int:
        """Prefill pad-bucket cost of admitting ``prompt``: the bucket
        of its UNCACHED suffix under prefix sharing, of the whole prompt
        otherwise. Never zero — a fully-cached prompt still prefills at
        least one token (its first-token logits), so suffix-budgeted
        admission can't spin on free admissions."""
        suffix = max(len(prompt) - self.prefix_cached_len(prompt), 1)
        return self.bucket(suffix)

    def _greedy_ops(self, vocab: int):
        """The greedy-default sampling operands of a ``vocab``-wide
        prefill (the target's or the draft's), built at construction."""
        return self._greedy[vocab]

    def _grammar_mask(self, sampling, stream: list):
        """Evaluate ``mask_fn`` over the emitted stream so far and
        validate its contract (bool ``[vocab]``, at least one token
        allowed) — the engine's one all-True mask when the request has
        none."""
        if sampling is None or sampling.mask_fn is None:
            return self._greedy_ops(self.vocab)[-1]
        mask = np.asarray(sampling.mask_fn(list(stream)), bool).reshape(-1)
        if mask.shape[0] != self.vocab:
            raise ValueError(
                f'mask_fn returned {mask.shape[0]} entries, the vocab is '
                f'{self.vocab}')
        if not mask.any():
            raise ValueError(
                'mask_fn allowed no token after '
                f'{len(stream)} emitted — a grammar must always leave an '
                'escape hatch (e.g. its stop token)')
        return jnp.asarray(mask)

    def _sampling_ops(self, sampling, emitted):
        """Per-request sampling operands for the FIRST token — position
        ``len(emitted)`` (the stream slots already journaled in a
        previous life). An unsampled request takes the construction-time
        greedy operands and brings only its position; a sampled one types
        its scalars on the host, so jitted programs never retrace on
        Python weak types and nothing is built on the device but a
        grammar mask."""
        seed, _, temp, topk, topp, unmasked = self._greedy_ops(self.vocab)
        position = np.int32(len(emitted))
        if sampling is None:
            return (seed, position, temp, topk, topp, unmasked)
        return (np.uint32(sampling.seed or 0), position,
                np.float32(sampling.temperature), np.int32(sampling.top_k),
                np.float32(sampling.top_p),
                self._grammar_mask(sampling, list(emitted)))

    def _run_prefill(self, decoder, bucket: int, padded, length: int,
                     ops=None, routed: bool = False):
        try:      # the plain program under the key it has always had
            run = _compiled_prefill(
                *((decoder, bucket, True) if routed else (decoder, bucket)))
        except TypeError:        # unhashable module field (e.g. live mesh)
            run = self._prefills.setdefault(
                (decoder is self._prefiller, bucket),
                _build_prefill(decoder, bucket, routed))
        if ops is None:
            ops = self._greedy_ops(decoder.vocab_size)
        return run(self._params if decoder is self._prefiller
                   else self._dparams, jnp.asarray(padded), length, *ops)

    def _prefill_rows(self, prompt, rows: list[int], ops):
        """Target prefill for an admission already seated in the pool:
        the resume program over the uncached suffix when the first row
        adopted a shareable prefix (and the suffix window fits), the
        plain full-prompt program otherwise. Returns the first token and
        the contiguous strip to adopt (valid at every prompt position at
        or past each row's own shared depth) — and, for a
        ``routing_sink``, the prompt's routing behind them."""
        shared = self.pool.shared_tokens(rows[0])
        suffix = prompt.size - shared
        if shared and shared + self.bucket(suffix) <= self.max_seq:
            sbucket = self.bucket(suffix)
            padded = np.zeros((1, sbucket), np.int32)
            padded[0, :suffix] = prompt[shared:]
            try:
                run = _compiled_resume(self._prefiller, sbucket)
            except TypeError:    # unhashable module field (e.g. live mesh)
                run = self._resumes.setdefault(
                    sbucket, _build_resume(self._prefiller, sbucket))
            first, prefill_cache = run(
                self._params, self._cache,
                jnp.asarray(self.pool.slots(rows[0])),
                jnp.asarray(padded), shared, suffix, *ops)
            self.sharing['resumed_prefills'] += 1
            return first, prefill_cache
        bucket = self.bucket(prompt.size)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :prompt.size] = prompt
        return self._run_prefill(self._prefiller, bucket, padded,
                                 prompt.size, ops,
                                 routed=self._routed is not None)

    def _validate(self, prompt, max_new: int, sampling=None) -> None:
        if prompt.size < 1:
            raise ValueError('empty prompt')
        if max_new < 1:
            raise ValueError(f'max_new must be >= 1, got {max_new}')
        if prompt.size + max_new > self.max_seq:
            raise ValueError(
                f'prompt ({prompt.size}) + max_new ({max_new}) exceeds the '
                f'cache capacity max_seq={self.max_seq}')
        self._validate_sampling(sampling)
        if self._spec:
            needed = prompt.size + max_new + self.speculate + 1
            if needed > self._drafter.max_seq:
                raise ValueError(
                    f'prompt + max_new + speculate + 1 = {needed} exceeds '
                    f'the draft cache capacity max_seq='
                    f'{self._drafter.max_seq} (the draft overshoots by up '
                    'to speculate tokens before rewinding)')

    def _validate_sampling(self, sampling) -> None:
        if sampling is None:
            return
        if sampling.sampled and sampling.seed is None:
            raise UnseededSampling(
                f'temperature {sampling.temperature} with no seed: the '
                'stream would not be reproducible, so journal replay, '
                'reroute, and hedging could not keep their token-exact '
                'contract — pass SamplingParams(seed=...)')
        if self._spec and sampling.mask_fn is not None:
            raise ValueError(
                'mask_fn does not compose with speculative rows — a '
                'grammar mask cannot update inside a multi-token verify '
                'window; serve structured requests on the plain engine')

    def _seat(self, prompt, max_new: int) -> tuple[int, list[int]]:
        """Claim a free row group and seat it in the pool (rolled back
        whole on a mid-flight block shortfall) — the Saturated half of
        admission, shared by :meth:`admit` and :meth:`admit_prefilled`."""
        if not self._free_rows:
            raise Saturated('no free row')
        if not self.can_admit(prompt.size, max_new, prompt=prompt):
            raise Saturated(
                f'{self.pool.blocks_for(prompt.size + max_new)} blocks '
                f'needed per row, {self.pool.free_blocks} free')

        fanout = self.tree_fanout if self._spec else 1
        rep = self._free_rows.pop()
        rows = list(range(rep, rep + fanout))
        tokens = prompt.size + max_new
        seated = []
        try:
            for row in rows:
                self.pool.admit(row, tokens,
                                prompt=prompt if self.share_prefix
                                else None)
                seated.append(row)
        except ValueError:
            for row in seated:
                self.pool.evict(row)
            self._free_rows.append(rep)
            raise Saturated(
                f'{self.pool.blocks_for(tokens)} blocks needed per row, '
                f'{self.pool.free_blocks} free') from None
        return rep, rows

    def _register(self, rep: int, rows: list[int], prompt, first: int,
                  max_new: int, stop_token: int | None, tag,
                  sampling=None, emitted=(), routing=None) -> Admission:
        """The host-side admission tail: sharing counters, row state,
        token/active mirrors, the seat program over the per-row device
        arrays, and the admitted-already-finished check."""
        fanout = self.tree_fanout if self._spec else 1
        self.sharing['admissions'] += 1
        self.sharing['prompt_tokens'] += int(prompt.size) * fanout
        shared_total = sum(self.pool.shared_tokens(row) for row in rows)
        self.sharing['shared_tokens'] += shared_total
        self.sharing['prefix_hits'] += bool(shared_total)

        seed = 0 if sampling is None or sampling.seed is None \
            else sampling.seed
        temp = 0.0 if sampling is None else sampling.temperature
        topk = 0 if sampling is None else sampling.top_k
        topp = 1.0 if sampling is None else sampling.top_p
        # the NEXT token's stream position: `first` just landed at
        # position len(emitted), so the step samples at len(emitted) + 1
        start = len(emitted) + 1
        self._tokens[rows] = first
        self._active[rows] = True
        with annotate('tpusystem.engine.seat'):
            (self._tokens_dev, self._active_dev, self._seed_dev,
             self._pos_dev, self._temp_dev, self._topk_dev,
             self._topp_dev) = self._seat_rows(
                self._donatable_tokens(), self._active_dev, self._seed_dev,
                self._pos_dev, self._temp_dev, self._topk_dev,
                self._topp_dev,
                np.array([rep, first, seed, start, topk], np.uint32),
                np.array([temp, topp], np.float32))
        self._rowstate[rep] = _RowState(tokens=[first], max_new=max_new,
                                        stop=stop_token, tag=tag,
                                        sampling=sampling,
                                        prior=tuple(emitted))
        if self._routed:
            self._rowstate[rep].prompt = prompt
            self._rowstate[rep].routing = [routing]
        self.sampled_rows += self._rowstate[rep].sampled
        reason = self._finish_reason(rep)
        if reason is not None:
            self.evict(rep)
            return Admission(rep, first, True, reason)
        if sampling is not None and sampling.mask_fn is not None:
            mask = self._grammar_mask(sampling, list(emitted) + [first])
            for row in rows:
                self._mask_dev = self._mask_dev.at[row].set(mask)
        return Admission(rep, first, False)

    @functools.cached_property
    def adoption(self) -> dict:
        """What admissions wrote into the pool: ``admissions`` (adoptions
        of a prefill strip), ``blocks`` (whole blocks written, those the
        prompts fill past a shared prefix, every row of a group) and
        ``shared_blocks`` (a shared prefix's blocks, left as they were)."""
        return {'admissions': 0, 'blocks': 0, 'shared_blocks': 0}

    def _adoption(self, rows: list[int],
                  length: int) -> tuple[list[np.ndarray], int]:
        """Each row's blocks for :func:`adopt_prefill` to write and how many
        that is, counted in :attr:`adoption`."""
        blocks = [self.pool.adoption_blocks(row, length) for row in rows]
        written = sum(int((ids < self.pool.blocks).sum()) for ids in blocks)
        self.adoption['admissions'] += 1
        self.adoption['blocks'] += written
        self.adoption['shared_blocks'] += sum(
            self.pool.shared_tokens(row) for row in rows) // self.block_size
        return blocks, written

    def _adopt(self, prefill_cache, rows: list[int],
               blocks: list[np.ndarray], length: int) -> None:
        """Write the blocks a prompt fills of a contiguous prefill strip
        into every row's own blocks, with the edited block tables."""
        for row, ids in zip(rows, blocks):
            self._cache = adopt_prefill(self._cache, prefill_cache,
                                        jnp.asarray(ids), self.pool.table,
                                        row, length)

    def admit(self, prompt, max_new: int, *, stop_token: int | None = None,
              tag=None, sampling=None, emitted=()) -> Admission:
        """Prefill ``prompt`` and seat it in a free row (a free GROUP of
        ``tree_fanout`` adjacent rows when speculative). Raises
        :class:`Saturated` when no row or not enough blocks are free
        (the scheduler queues on this), ``ValueError`` on requests that
        could never fit — :class:`UnseededSampling` among them.

        ``sampling`` is the request's :class:`SamplingParams` (None =
        greedy); ``emitted`` the tokens a previous life already emitted
        for this request (journal replay passes its prefix here so
        sampling positions continue where the stream left off — the
        prompt must already include those tokens)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self._validate(prompt, max_new, sampling)
        ops = self._sampling_ops(sampling, emitted)
        rep, rows = self._seat(prompt, max_new)

        # the tpusystem.engine.* spans sit on the brackets `timings`
        # times, so a device trace and the accumulators agree
        started = time.perf_counter()
        with annotate('tpusystem.engine.prefill'):
            first, prefill_cache, *routing = self._prefill_rows(
                prompt, rows, ops)
        self.timings['prefill'] += time.perf_counter() - started

        started = time.perf_counter()
        blocks, written = self._adoption(rows, prompt.size)
        with annotate('tpusystem.engine.adopt', blocks=written):
            self._adopt(prefill_cache, rows, blocks, prompt.size)
            if self._spec:
                dbucket = prefill_bucket(prompt.size, self.block_size,
                                         self._drafter.max_seq)
                padded = np.zeros((1, dbucket), np.int32)
                padded[0, :prompt.size] = prompt
                _, draft_cache = self._run_prefill(self._draft_prefiller,
                                                   dbucket, padded,
                                                   prompt.size)
                self._dcache = _adopt_draft_rows(
                    self._dcache, draft_cache, jnp.asarray(rows, jnp.int32),
                    prompt.size)
        self.timings['admit'] += time.perf_counter() - started

        # the first token is read once the adoption is dispatched: the
        # host's adoption runs while the chip computes the prefill
        started = time.perf_counter()
        with annotate('tpusystem.engine.prefill'):
            first = int(first)
        self.timings['prefill'] += time.perf_counter() - started
        return self._register(
            rep, rows, prompt, first, max_new, stop_token, tag, sampling,
            emitted, routing=_prompt_routing(routing, prompt.size,
                                             self._selections)
            if routing else None)

    # ------------------------------------------------- disaggregated prefill

    def export_prefill(self, prompt, *, sampling=None,
                       emitted=()) -> tuple[int, dict]:
        """Run the admission prefill WITHOUT seating a row — the
        prefill-role half of disaggregated serving. Returns ``(first,
        kv)``: the prompt's first token and every layer's contiguous KV
        strip (``keystr path -> [1, max_seq, heads, head_dim]`` numpy,
        host-side so the blob plane can ship it), a recurrent layer's
        per-row state leaves (``state``/``conv`` as they stood at the
        prompt's true length) among them under their own paths. The decode-role
        replica seats it with :meth:`admit_prefilled`; this engine's
        pool, rows and sharing index are untouched. A sampled request's
        first token samples at its ``(seed, len(emitted))`` counter —
        a pure function, so the prefill replica's choice is exactly
        what the decode replica would have computed itself."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError('empty prompt')
        if prompt.size >= self.max_seq:
            raise ValueError(
                f'prompt ({prompt.size}) leaves no decode room under '
                f'max_seq={self.max_seq}')
        self._validate_sampling(sampling)
        ops = self._sampling_ops(sampling, emitted)
        bucket = self.bucket(prompt.size)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :prompt.size] = prompt
        started = time.perf_counter()
        with annotate('tpusystem.engine.prefill'):
            first, prefill_cache = self._run_prefill(
                self._prefiller, bucket, padded, prompt.size, ops)
            first = int(first)
        self.timings['prefill'] += time.perf_counter() - started
        from tpusystem.train.cursors import is_by_row
        kv = {jax.tree_util.keystr(path): np.asarray(leaf)
              for path, leaf
              in jax.tree_util.tree_leaves_with_path(prefill_cache)
              if _is_kv(path) or is_by_row(path)}
        return first, kv

    def _strip_cache(self, kv: dict):
        """Rebuild a contiguous prefill cache pytree from exported KV
        strips — the receiving half of :meth:`export_prefill`. Missing
        or misshapen strips raise ``ValueError`` (prefill and decode
        replicas must serve the same module geometry)."""
        shapes = jax.eval_shape(
            functools.partial(self._prefiller.init, jax.random.PRNGKey(0)),
            jnp.zeros((1, 1), jnp.int32))['cache']

        from tpusystem.train.cursors import is_by_row

        def fill(path, leaf):
            if not (_is_kv(path) or is_by_row(path)):
                return jnp.zeros(leaf.shape, leaf.dtype)
            name = jax.tree_util.keystr(path)
            if name not in kv:
                raise ValueError(
                    f'handoff strip missing KV leaf {name} — prefill and '
                    'decode replicas must serve the same module')
            strip = kv[name]
            if tuple(strip.shape) != tuple(leaf.shape):
                raise ValueError(
                    f'handoff strip {name} is {tuple(strip.shape)}, this '
                    f'engine expects {tuple(leaf.shape)} — prefill and '
                    'decode replicas must serve the same module geometry')
            return jnp.asarray(strip, leaf.dtype)
        return jax.tree_util.tree_map_with_path(fill, shapes)

    def admit_prefilled(self, prompt, max_new: int, first: int, kv: dict,
                        *, stop_token: int | None = None, tag=None,
                        sampling=None, emitted=()) -> Admission:
        """Seat a request whose prefill ran on ANOTHER engine
        (:meth:`export_prefill` strips, shipped over the blob plane).
        Same contract as :meth:`admit` — Saturated when nothing is free,
        ValueError on requests that could never fit — but the only
        device work is the existing ``adopt_prefill``/``write_tables``
        admission seam: no prefill program runs here, which is the whole
        point of the disaggregated split."""
        if self._spec:
            raise ValueError(
                'admit_prefilled does not compose with speculative rows — '
                'the draft cache has no handoff strip; disaggregate the '
                'plain engine')
        if self._routed:
            raise ValueError(
                'a routing_sink covers plain admission only: this prefill '
                'went through the experts on another engine')
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self._validate(prompt, max_new, sampling)
        prefill_cache = self._strip_cache(kv)     # validate BEFORE seating
        rep, rows = self._seat(prompt, max_new)

        started = time.perf_counter()
        blocks, written = self._adoption(rows, prompt.size)
        with annotate('tpusystem.engine.adopt', blocks=written):
            self._adopt(prefill_cache, rows, blocks, prompt.size)
        self.timings['admit'] += time.perf_counter() - started
        return self._register(rep, rows, prompt, int(first), max_new,
                              stop_token, tag, sampling, emitted)

    def prefix_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from the radix
        index (0.0 before any admission)."""
        total = self.sharing['prompt_tokens']
        return self.sharing['shared_tokens'] / total if total else 0.0

    def _finish_reason(self, row: int) -> str | None:
        state = self._rowstate[row]
        if state.stop is not None and state.tokens[-1] == state.stop:
            return 'stop'
        if len(state.tokens) >= state.max_new:
            return 'length'
        return None

    # ------------------------------------------------------------- decoding

    # the plain tick dispatched ahead by the last step() and not yet read
    _flight: _Tick | None = None

    @functools.cached_property
    def overlap(self) -> dict:
        """Plain decode ticks by how they were dispatched: ``ahead`` (while
        the tick before them was still in flight, unread), ``synchronous``
        (with no tick in flight), and ``dead_rows``: rows a tick computed
        ahead whose token was dropped, because the request that held the
        row when the tick was dispatched left it before the read. The
        speculative step is outside this count."""
        return {'ahead': 0, 'synchronous': 0, 'dead_rows': 0}

    @functools.cached_property
    def _cleared(self) -> set:
        """Rows the clear program already retired on the device, before the
        tick ahead was dispatched, whose host side :meth:`evict` still owes
        after the read."""
        return set()

    def step(self) -> StepReport:
        """Advance every active row: one token per request on the plain
        step (greedy or sampled, per the row's :class:`SamplingParams`), up
        to ``speculate + 1`` on the speculative step. Retires rows that hit
        their length or stop token.

        **One plain tick stays in flight across calls.** A call dispatches
        the next tick on the device outputs of the tick in flight (tokens,
        cache and positions feed back device to device; the step donates
        only the cache), then reads the older tick's tokens, walks its rows
        and returns its report; with no tick in flight it first dispatches
        one. The chip computes the tick ahead while the host reads, walks,
        narrates, admits and dispatches. The reports come in the order a
        synchronous engine gives them; a row seated between two calls
        first decodes in the tick the second call dispatches, so its second
        token arrives one call later (its first comes from the prefill).

        * **Attribution.** Each dispatch keeps which :class:`_RowState` held
          each active row; a token is credited only to that request. A row
          evicted, cancelled, expired or re-seated while its tick was in
          flight gets nothing from that tick: not its tokens, the
          journal, its routing or a ``routing_sink`` (``dead_rows``).
        * **Length is known before the read.** A row whose token in the
          tick in flight is its ``max_new``-th is cleared on the device
          (the clear program) before the tick ahead is dispatched; the
          host half of :meth:`evict` (blocks, table, free row, sink) runs
          after the read. So a row runs a *dead* tick (active, its token
          dropped) only when it finishes on its stop token or leaves
          between calls, and then it took fewer than ``max_new`` tokens:
          the dead write lands at its cursor, under ``prompt + max_new <=
          max_seq``, inside the blocks it still owned when the tick was
          dispatched. A cleared row's write, one position further, lands
          there too (its last reserved position); from the next tick its
          table points at trash.
        * **Why a dead write is harmless.** Blocks return to the free list
          only after the read, so any admission that adopts them (or
          writes any row's table) is dispatched after the tick, and the
          device runs programs in the order they were dispatched. A read
          masks every position past its row's cursor, and a position is
          written by its own tick before any read reaches it. A recurrent
          layer's per-row state and convolution tail
          (``cursors.is_row_state``) are overwritten whole by
          ``adopt_prefill`` when the row is seated again. Under
          ``share_prefix`` the radix index keeps only blocks a prompt
          fully covers, and a row's cursor never stands in one (decode
          writes start at the prompt's length), so no dead write lands in
          a retained block.
        * **When a call runs synchronously** (dispatch, read, walk: as
          before), from the engine's own state: when an active row holds
          a grammar ``mask_fn`` (its next mask needs this tick's token on
          the host), and on the speculative step. A tick in flight is then
          read and reported first, with nothing dispatched after it. If
          the active set empties while a tick is in flight, the call reads
          and discards it.

        ``timings['step']`` (and ``last_step_seconds``) is the dispatch
        ahead plus the wait for the read."""
        if self._spec:
            return self._spec_tick() if self._active.any() \
                else StepReport({}, [])
        started = time.perf_counter()
        tick, self._flight = self._flight, None
        if tick is None:
            if not self._active.any():
                return StepReport({}, [])
            tick = self._dispatch(ahead=False)
        if not any(state.sampling is not None
                   and state.sampling.mask_fn is not None
                   for state in self._rowstate.values()):
            self._flight = self._dispatch_ahead(tick)
        with annotate('tpusystem.engine.read'):
            read = self._settled(tick)
        self.last_step_seconds = time.perf_counter() - started
        self.timings['step'] += self.last_step_seconds
        return self._walk(tick, read)

    def _dispatch(self, ahead: bool) -> _Tick:
        """Dispatch one plain tick over the rows active on the device; its
        tokens become the operand of the next."""
        rows = {row: self._rowstate[row]
                for row in map(int, np.flatnonzero(self._active))
                if row not in self._cleared}
        with annotate('tpusystem.engine.dispatch',
                      select=self._count_selection(
                          any(state.sampled for state in rows.values())),
                      ahead=int(ahead)):
            self._tokens_dev, self._cache, self._pos_dev, *counted = \
                self._step(self._params, self._cache, self._tokens_dev,
                           self._active_dev, self._seed_dev, self._pos_dev,
                           self._temp_dev, self._topk_dev, self._topp_dev,
                           self._mask_dev)
        # retired rows' stale device token stays as-is (in-vocab junk an
        # inactive row may keep embedding — masked, never emitted)
        read = counted[0] if counted else self._tokens_dev
        read.copy_to_host_async()
        words = counted[1] if self._selections else None
        if words is not None:
            words.copy_to_host_async()
        self.overlap['ahead' if ahead else 'synchronous'] += 1
        return _Tick(read, rows, bool(counted), words=words)

    def _dispatch_ahead(self, tick: _Tick) -> _Tick | None:
        """Clear on the device every row whose token in ``tick`` is its
        last, then dispatch the next tick if any row is still active."""
        for row, seated in tick.rows.items():
            if (self._rowstate.get(row) is seated
                    and len(seated.tokens) + 1 >= seated.max_new):
                self._clear(row)
                self._cleared.add(row)
        if len(self._cleared) == int(self._active.sum()):
            return None
        return self._dispatch(ahead=True)

    def _settled(self, tick: _Tick) -> np.ndarray:
        """The tick's read on the host (waiting for the device if need
        be), kept on the tick."""
        if tick.host is None:
            tick.host = np.asarray(tick.read)
        return tick.host

    def _donatable_tokens(self) -> jax.Array:
        """The token array, for the seat program that donates it: where the
        tick in flight reads that very array, its read is taken first (an
        admission's prefill has already waited for the device)."""
        tick = self._flight
        if tick is not None and tick.read is self._tokens_dev:
            started = time.perf_counter()
            with annotate('tpusystem.engine.read'):
                self._settled(tick)
            self.timings['step'] += time.perf_counter() - started
        return self._tokens_dev

    def _walk(self, tick: _Tick, read: np.ndarray) -> StepReport:
        """Credit the tick's tokens to the requests that held its rows when
        it was dispatched, retire those that finished, and report."""
        token, routing = read, None
        if tick.counted:
            token, routing = self._split_read(read)
        if tick.words is not None:           # routing_selections
            routing = np.concatenate([routing.reshape(self.rows, -1),
                                      np.asarray(tick.words)], axis=1)
        emitted, finished = {}, []
        with annotate('tpusystem.engine.rows'):
            for row, seated in tick.rows.items():
                state = self._rowstate.get(row)
                if state is not seated:      # left while the tick flew
                    self.overlap['dead_rows'] += 1
                    continue
                self._tokens[row] = int(token[row])
                emitted[row] = [int(token[row])]
                if routing is not None:      # of the token this tick took in
                    state.routing.append(routing[row][None])
                state.tokens.append(int(token[row]))
                reason = self._finish_reason(row)
                if reason is not None:
                    state = self.evict(row)
                    finished.append((row, reason, list(state.tokens)))
                elif (state.sampling is not None
                      and state.sampling.mask_fn is not None):
                    # the grammar hook: re-evaluate the mask over the full
                    # stream so the NEXT position sees it — a host-side
                    # fixed-shape row write, never a retrace
                    mask = self._grammar_mask(
                        state.sampling,
                        list(state.prior) + list(state.tokens))
                    self._mask_dev = self._mask_dev.at[row].set(mask)
        return StepReport(emitted, finished, self.last_expert_load)

    def _split_read(self, read: np.ndarray):
        """The tick's read into its tokens and what the expert layers put
        behind them: their counters, under the names they sow them by
        (kept as the tick's own in ``last_expert_load`` and as running sums
        in ``expert_load``), and for a ``routing_sink`` every row's
        ``[expert layers, k]`` choices. Returns ``(tokens, routing |
        None)``."""
        names, routed = self._load_names, self._routed or (0, 0)
        assert read.size == self.rows * (1 + routed[0] * routed[1]) \
            + len(names), (read.size, self.rows, names, routed)
        counts = read[self.rows:self.rows + len(names)]
        if names:
            self.last_expert_load = {name: int(count) for name, count
                                     in zip(names, counts)}
            self.expert_load['ticks'] += 1
            for name, count in self.last_expert_load.items():
                self.expert_load[name] += count
        routing = None
        if self._routed:
            routing = read[self.rows + len(names):].reshape(
                self.rows, *routed)
        return read[:self.rows], routing

    def _count_selection(self, sampled: bool | None = None) -> str:
        """Count the decode dispatch about to run on the side of
        ``select_tokens``' cond it will take, and name that side (the
        ``select`` stat of the ``tpusystem.engine.dispatch`` span):
        ``sampled`` when a row active on the device samples, by default
        when a seated row does."""
        if sampled is None:
            sampled = bool(self.sampled_rows)
        kind = 'sampled' if sampled else 'greedy'
        self.selection[f'{kind}_ticks'] += 1
        return kind

    def lowered_step(self, debug_info: bool = False) -> str:
        """The plain decode step as lowered text on the engine's own
        operands — what ``chip_smoke.py`` greps for the Mosaic custom
        call; with ``debug_info`` every operation carries its
        ``named_scope`` path as a location. Lowering re-traces, so the
        ``trace_count`` witness is put back."""
        traces = self.trace_count
        try:
            return self._step.lower(
                self._params, self._cache, self._tokens_dev,
                self._active_dev, self._seed_dev, self._pos_dev,
                self._temp_dev, self._topk_dev, self._topp_dev,
                self._mask_dev).as_text(debug_info=debug_info)
        finally:
            self.trace_count = traces

    def _spec_tick(self) -> StepReport:
        started = time.perf_counter()
        with annotate('tpusystem.engine.dispatch',
                      select=self._count_selection()):
            emitted_dev, accepted_dev, self._tokens_dev, self._cache, \
                self._dcache, self._pos_dev = self._spec_step(
                    self._params, self._dparams, self._cache, self._dcache,
                    self._tokens_dev, self._active_dev, self._seed_dev,
                    self._pos_dev, self._temp_dev, self._topk_dev,
                    self._topp_dev, self._mask_dev)
        with annotate('tpusystem.engine.read'):
            window = np.asarray(emitted_dev)             # [groups, K+1]
            accepted = np.asarray(accepted_dev)
        self.last_step_seconds = time.perf_counter() - started
        self.timings['step'] += self.last_step_seconds
        fanout = self.tree_fanout
        emitted, finished = {}, []
        with annotate('tpusystem.engine.rows'):
            for rep in sorted(self._rowstate):
                if not self._active[rep]:
                    continue
                state = self._rowstate[rep]
                group = rep // fanout
                count = int(accepted[group]) + 1
                toks = [int(t) for t in window[group, :count]]
                # host truncation happens only at a finish (budget or
                # stop), so the device cursors' extra advance dies with
                # the evict
                toks = toks[:state.max_new - len(state.tokens)]
                if state.stop is not None and state.stop in toks:
                    toks = toks[:toks.index(state.stop) + 1]
                state.tokens.extend(toks)
                for row in range(rep, rep + fanout):
                    self._tokens[row] = toks[-1]
                emitted[rep] = toks
                reason = self._finish_reason(rep)
                if reason is not None:
                    state = self.evict(rep)
                    finished.append((rep, reason, list(state.tokens)))
        return StepReport(emitted, finished)

    # ------------------------------------------------------------- eviction

    def evict(self, row: int) -> _RowState:
        """Retire ``row`` (finished or cancelled; the representative row
        when speculative — its whole branch group retires): its blocks
        return to the free list, its table resets to trash, its rows go
        back to the idle greedy default — a host-side edit, the clear
        program and one fixed-shape table write, never a retrace."""
        if row not in self._rowstate:
            raise ValueError(f'row {row} is not seated')
        fanout = self.tree_fanout if self._spec else 1
        state = self._rowstate[row]
        for member in range(row, row + fanout):
            self.pool.evict(member)
        self._active[row:row + fanout] = False
        self._tokens[row:row + fanout] = 0
        if row in self._cleared:     # the device half ran before a dispatch
            self._cleared.discard(row)
        else:
            self._clear(row)
        self.sampled_rows -= state.sampled
        self._cache = write_tables(self._cache, self.pool.table)
        self._free_rows.append(row)
        if self._routed:
            self._routing_sink(state.tag, state.prompt, list(state.tokens),
                               _compact(np.concatenate(state.routing)))
        return self._rowstate.pop(row)

    def _clear(self, row: int) -> None:
        """The clear program over ``row``'s group: inactive, greedy,
        unmasked on the device."""
        self._active_dev, self._temp_dev, self._mask_dev = self._clear_rows(
            self._active_dev, self._temp_dev, self._mask_dev, np.int32(row))

    def tokens(self, row: int) -> list:
        """Tokens emitted so far for a seated row."""
        return list(self._rowstate[row].tokens)

    @property
    def recurrence(self) -> dict:
        """How the decode step's stack runs a token, from the module and the
        cache's shapes: ``passes`` (how many times the stack runs: a looped
        model's ``passes``, else 1), ``cache_layers`` (the attention
        applications that keep a cache of their own: one ``key`` leaf each,
        every pass of every layer in a looped model) and
        ``kv_bytes_per_position`` (the keys and values one cached position
        holds across them). The scheduler marks it once in its tracer:
        ``recurrence``."""
        pools = [(path[-1].key, leaf) for path, leaf
                 in jax.tree_util.tree_leaves_with_path(self._cache)
                 if _is_kv(path)]
        return {'passes': getattr(self._decoder, 'passes', 1),
                'cache_layers': sum(name == 'key' for name, _ in pools),
                'kv_bytes_per_position': sum(
                    leaf.shape[-1] * leaf.dtype.itemsize for _, leaf in pools)}


@dataclasses.dataclass
class _Tick:
    """A dispatched plain decode tick: the array the host reads (its
    tokens, with the expert layers' counters and routing behind them when
    ``counted``), the request that held each active row at dispatch, and
    the read once it reached the host."""
    read: jax.Array
    rows: dict                       # row -> _RowState
    counted: bool
    host: np.ndarray | None = None
    words: jax.Array | None = None   # the selections, for routing_selections


# ----------------------------------------- window and learned sparse attention

def _cache_kind(path) -> str | None:
    """Which part of the cache a leaf is, for ``Engine.cache_bytes``:
    ``'kv'`` (the paged pool's keys, values or latent rows), ``'index'``
    (a sparse-attention indexer's keys, paged beside them), ``'state'``
    (a recurrent layer's per-row state), ``'window'`` (a window layer's
    per-row window cache); ``None`` for tables and cursors."""
    from tpusystem.train.cursors import is_window
    if _is_kv(path):
        indexer = jax.tree_util.DictKey('indexer') in path
        return 'index' if indexer else 'kv'
    if is_row_state(path):
        return 'state'
    return 'window' if is_window(path) else None


def is_windowed(module) -> bool:
    """Whether the module's decode cache holds a window cache leaf, read
    from the shapes its decode clone declares."""
    from tpusystem.train.cursors import holds_window
    shapes = jax.eval_shape(
        functools.partial(_decoder(module).init, jax.random.PRNGKey(0)),
        jnp.zeros((1, 1), jnp.int32))
    return holds_window(shapes.get('cache', {}))


def window_reason(module, *, share_prefix: bool = False, draft_module=None,
                  sharded: bool = False) -> str | None:
    """Why the engine cannot serve this module with these options, where a
    window cache (:func:`is_windowed`) is in the way; else ``None``."""
    wanted = [
        (share_prefix, 'share_prefix=True', 'a shared prefix is adopted by '
         'blocks of the pool, and a window cache has no blocks: a row would '
         "need the prefix's window rows copied in at admission"),
        (draft_module is not None, 'a draft module (speculative rows)',
         'a rejected draft token cannot be rewound out of a window cache: '
         'its slot overwrote an older position the row still attends '
         '(cursors.rewind refuses it)'),
        (sharded, 'a mesh', 'the window cache has no sharding contract '
         '(pool_shardings shards keys and values by head)')]
    for asked, name, why in wanted:
        if asked and any(is_windowed(each) for each in (module, draft_module)
                         if each is not None):
            return (f'{name} does not compose with a window cache '
                    f'({type(module).__name__}): {why}')
    return None


def sparse_attention_of(decoder, cache) -> dict | None:
    """How a decoder with window and learned sparse attention layers reads
    its cache (``None`` for any other): ``index_topk`` and ``window`` (the
    module's), how many layers of each kind, the indexer's bytes a
    position (``index`` leaves) and the window cache's bytes a row
    (``window`` leaves), and which read each kind takes: ``sparse_read``
    ``'kernel'`` (the indexer's and the latent pool's block walks, the
    selection a mask: every held row fetched) or ``'gather'`` (whole rows
    gathered through the table, off the TPU or where a plan refuses the
    shapes); ``window_read`` ``'window cache'`` (the row's window cache
    read whole, masked by the position each slot holds). The scheduler
    marks it once in its tracer: ``sparse_attention``."""
    if not hasattr(decoder, 'index_topk'):
        return None
    leaves = jax.tree_util.tree_leaves_with_path(cache)
    index = [leaf for path, leaf in leaves if _cache_kind(path) == 'index']
    window = [leaf for path, leaf in leaves if _cache_kind(path) == 'window']
    read = 'gather'
    if on_tpu() and decoder.decode_pages is not None:
        from tpusystem.ops.pallas.index_scores import index_plan
        from tpusystem.ops.pallas.latent_attention import latent_plan
        blocks, block = decoder.decode_pages
        max_blocks = decoder.max_seq // block
        if index and index_plan(
                decoder.index_heads, decoder.index_dim, block, max_blocks,
                index[0].dtype, False) is not None and latent_plan(
                decoder.heads, decoder.kv_rank, block, max_blocks,
                index[0].dtype, False) is not None:
            read = 'kernel'
    return {'index_topk': decoder.index_topk,
            'window': decoder.window,
            'full_layers': len(index), 'window_layers': len(window),
            'index_bytes_per_position': sum(
                leaf.shape[-1] * leaf.dtype.itemsize for leaf in index),
            'window_bytes_per_row': sum(
                leaf.size * leaf.dtype.itemsize // leaf.shape[0]
                for leaf in window),
            'sparse_read': read, 'window_read': 'window cache'}
