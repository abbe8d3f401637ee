"""Pipeline parallelism — microbatch schedule over the ``stage`` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.4: "PP | absent");
this module supplies it TPU-natively: layers are stacked into a leading
``layers`` dimension, that dimension is sharded over the ``stage`` axis (so
each device owns ``layers / stages`` contiguous layers), and microbatch
activations travel stage-to-stage with ``ppermute`` over the ICI ring inside
``shard_map``.

Schedule: GPipe. All microbatch forwards stream through the pipe; XLA's
autodiff of the tick ``lax.scan`` then replays the schedule in reverse, so
the backward pass drains the pipe stage-by-stage in the transposed order —
the same bubble fraction as hand-written 1F1B, ``(S-1)/(M+S-1)``, with
memory bounded by per-microbatch rematerialisation (``remat=True`` wraps
each stage body in ``jax.checkpoint``, so live activations are O(M) *block
inputs*, not O(M·L) intermediates).

Composition: the batch dimension stays sharded over ``(data, fsdp)``, so
DP×PP works out of the box. Tensor parallelism *within* a stage works via
*partial-manual* ``shard_map``: the pipeline is manual over the
``(data, fsdp, stage)`` axes only (``axis_names=``), leaving the ``model``
axis to GSPMD **inside** the stage bodies — stacked params placed
``P(stage, ..., model)`` (see :func:`PipelineParallel`'s
``stacked_rules``) keep their model-axis sharding through the shard_map
boundary, and GSPMD partitions each stage's matmuls over ``model`` with
the usual Megatron collectives, composed with the manual ``ppermute``
ring over ``stage``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from tpusystem.parallel.mesh import DATA, FSDP, MODEL, STAGE
from tpusystem.parallel.sharding import ShardingPolicy

# One layer of the pipelined stack: (layer_params, activations) -> activations
BlockFn = Callable[[Any, jax.Array], jax.Array]


def _unit_runner(mesh):
    """How schedule units execute on this mesh: ``run_unit(predicate, run,
    skip)``.

    Without a live ``model`` axis, idle (fill/drain/pad) ticks *skip* the
    unit body via ``lax.cond`` — inside shard_map, cond on a
    device-varying predicate is real per-device control flow. With
    ``model > 1`` the stage bodies carry GSPMD-inserted model collectives
    (TP all-reduces, resharding permutes), and a collective may never sit
    under control flow that only some participants take: devices must
    issue every collective in lockstep (XLA:CPU's in-process rendezvous
    deadlocks outright; on any backend non-uniform collective execution
    is undefined SPMD). So under PP x TP every unit executes *masked* —
    both paths run, ``jnp.where`` keeps the active one.

    Masked cost: *block* units pay only the fill/drain bubble's worth of
    extra FLOPs (they were active on ~all non-bubble ticks anyway, and
    idle devices sit in lockstep either way). The 1F1B *head/tail* units
    are different: masked, they run on every stage at every round instead
    of once per microbatch on one stage — up to ~S x redundant head/tail
    work. Under PP x TP keep the per-tick tail light (chunked/fused loss,
    ``return_features``) or use the GPipe path (:func:`pipeline_apply`),
    whose head and tail run outside the pipe entirely."""
    if mesh.shape.get(MODEL, 1) == 1:
        return lambda predicate, run, skip: lax.cond(predicate, run, skip)

    def masked(predicate, run, skip):
        return jax.tree.map(
            lambda a, b: jnp.where(predicate, a, b), run(), skip())
    return masked


def _needs_jit_wrap(mesh) -> bool:
    """Partial-manual shard_map (live model axis) only traces under jit,
    so PP x TP calls are wrapped unconditionally — a non-jit trace
    context (eager ``jax.grad``, ``vmap``, ``eval_shape``) needs the
    wrapper just as plain eager execution does, and under an outer jit
    the nested jit is cheap. The wrapper (and the traced schedule inside
    it) is memoized per stacked-params structure in
    :func:`pipeline_train`, so eager PP x TP callers compile once and
    replay from jit's cache; without a model axis the runner is a bare
    ``shard_map`` and eager callers still pay per-call tracing — jit the
    surrounding step for anything hot."""
    return mesh.shape.get(MODEL, 1) > 1


def _manual_axes(mesh) -> frozenset:
    """Mesh axes the pipeline handles manually inside ``shard_map``.

    With a live ``model`` axis, only the axes whose collectives the
    schedule issues itself (batch ``psum``, stage ``ppermute``) are
    manual; ``model`` stays *auto* — GSPMD sees through the shard_map
    boundary there, so model-axis-sharded stacked params keep their
    sharding and the stage bodies partition over ``model`` with
    GSPMD-inserted collectives (Megatron TP within a stage). Partial
    manualness currently traces only under ``jit`` (eager shard_map
    rejects it), so the degenerate model=1 mesh keeps the classic fully
    manual mapping — identical semantics, eager-friendly. Every axis
    except ``model`` stays manual either way (a block_fn issuing its own
    seq/expert collectives keeps working under PP x TP)."""
    if mesh.shape.get(MODEL, 1) == 1:
        return frozenset(mesh.axis_names)
    return frozenset(mesh.axis_names) - {MODEL}


def pipeline_apply(block_fn: BlockFn, stacked_params: Any, hidden: jax.Array,
                   mesh, *, microbatches: int, remat: bool = True,
                   interleave: int = 1, schedule=None,
                   has_aux: bool = False):
    """Run ``hidden`` through a layer stack pipelined over ``stage``.

    Args:
        block_fn: pure per-layer function ``(layer_params, x) -> x`` —
            or ``(layer_params, x) -> (x, aux)`` with ``has_aux=True``.
        stacked_params: pytree whose leaves carry a leading ``layers``
            dimension (e.g. built with ``jax.vmap(block.init)``); ``layers``
            must be divisible by the mesh's ``stage`` size. With
            ``interleave = v > 1`` the leaves are chunk-major
            ``[v, layers/v, ...]`` (a plain reshape of the layer-major
            stack) sharded ``P(None, stage)`` — the same layout contract as
            :func:`pipeline_train`.
        hidden: global activations ``[batch, ...]``; batch must divide by
            ``data*fsdp*microbatches``.
        mesh: mesh with a ``stage`` axis (size 1 degenerates gracefully).
        microbatches: how many microbatches to stream through the pipe.
        interleave: virtual-pipeline chunks per device. ``v > 1`` shrinks
            the forward fill/drain bubble from ``S-1`` stage-units to
            ``(S-1)/v`` (microbatches ride the ring ``v`` times through
            chunk-sized units — the schedule of :func:`pipeline_train`'s
            forward slot). Microbatch counts that don't divide the stage
            count pad the last chunk sweep with idle units (the intrinsic
            ring-latency bubble of a short group — see
            :func:`pipeline_train`).
        schedule: optional :class:`~tpusystem.parallel.schedule
            .OverlapSchedule`. With ``pp='overlap'`` the loop takes the
            *skewed double-buffered* tick: each stage issues the
            ``ppermute`` of last tick's output at tick top — under this
            tick's stage compute, which consumes the message received a
            tick earlier — so every stage-to-stage transfer rides under a
            microbatch's matmuls instead of sitting on the tick-to-tick
            critical path (the classic tick sends *after* the compute
            that produced the message, so the next tick's compute waits
            out the wire). Price: one extra fill tick per stage
            (``M + 2(S-1)`` ticks vs ``M + S - 1``). The hop is the
            :func:`~tpusystem.parallel.collectives.pp_hop` custom_vjp,
            so autodiff's reversed sends hide under the backward matmuls
            the same way; both schedules compute identical math on
            identical operands — outputs are **bitwise-equal**. The pure
            :func:`~tpusystem.parallel.schedule.pp_plan` pins the
            classic fallback (microbatch rows won't split into
            ``schedule.chunks`` ppermutes, or ``interleave > 1``).
        has_aux: ``block_fn`` returns ``(x, aux_scalar)`` per unit (the
            MoE router losses); the call returns ``(hidden, aux)`` with
            ``aux`` the mean over every (unit, microbatch) — summed over
            stages, averaged over batch shards.
    """
    stages = mesh.shape[STAGE]
    chunks = interleave
    leading = jax.tree.leaves(stacked_params)[0].shape[:2]
    if chunks > 1 and leading[0] != chunks:
        raise ValueError(
            f'interleave={chunks} expects chunk-major stacked params '
            f'[{chunks}, layers/{chunks}, ...]; got leading dims {leading}')
    layers = leading[0] if chunks == 1 else chunks * leading[1]
    if layers % (stages * chunks):
        raise ValueError(f'{layers} layers not divisible by {stages} stages '
                         f'x {chunks} chunks')
    data_parallel = mesh.shape[DATA] * mesh.shape[FSDP]
    if hidden.shape[0] % (data_parallel * microbatches):
        raise ValueError(
            f'batch {hidden.shape[0]} not divisible by data*fsdp*microbatches '
            f'= {data_parallel}*{microbatches}')
    batch_axes = (DATA, FSDP) if data_parallel > 1 else None
    activation_spec = P(batch_axes, *([None] * (hidden.ndim - 1)))
    chunk_spec = P(STAGE) if chunks == 1 else P(None, STAGE)
    param_specs = jax.tree.map(lambda _: chunk_spec, stacked_params)
    # a partial last group pads with idle units (see pipeline_train)
    padded = (microbatches if chunks == 1
              else -(-microbatches // stages) * stages)

    # the pp= arm: the pure plan decides skewed-overlap vs classic ticks
    # from the per-device microbatch's leading dimension (what pp_hop's
    # chunked ppermute splits)
    from tpusystem.parallel.schedule import PpPlan, pp_plan
    micro_rows = hidden.shape[0] // data_parallel // microbatches
    if schedule is not None and schedule.pp == 'overlap':
        plan = pp_plan(micro_rows, stages, chunks=schedule.chunks,
                       interleave=interleave)
    else:
        plan = PpPlan('skip', 1, 'pp overlap inactive')

    stage_body = _stage_scan(block_fn, has_aux=has_aux)
    if remat:
        stage_body = jax.checkpoint(stage_body)
    run_unit = _unit_runner(mesh)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(param_specs, activation_spec),
        out_specs=(activation_spec, P()) if has_aux else activation_spec,
        check_vma=False,
        axis_names=_manual_axes(mesh))
    def pipelined(params, local_hidden):
        stage = lax.axis_index(STAGE)
        count = lax.axis_size(STAGE)
        shape = (microbatches, local_hidden.shape[0] // microbatches)
        batches = local_hidden.reshape(shape + local_hidden.shape[1:])
        if chunks == 1:
            params_all = jax.tree.map(lambda leaf: leaf[None], params)
        else:
            params_all = params
        span = chunks * count

        def unit_out(params_c, x):
            out = stage_body(params_c, x)
            return out if has_aux else (out, jnp.float32(0))

        def idle_out(x):
            return jnp.zeros_like(x), jnp.float32(0)

        def schedule_unit(unit):
            """Unit index -> (active, chunk, microbatch) — the forward slot
            of pipeline_train's interleaved schedule; for chunks == 1 it
            reduces to (0 <= unit < M, 0, unit)."""
            group, rem = jnp.divmod(unit, span)
            chunk, pos = jnp.divmod(rem, count)
            m = group * count + pos
            active = ((unit >= 0) & (unit < chunks * padded)
                      & (m < microbatches))
            return (active, jnp.clip(chunk, 0, chunks - 1),
                    jnp.clip(m, 0, microbatches - 1))

        def classic_tick(state, t):
            active, c_f, m_f = schedule_unit(t - stage)
            feed = lax.dynamic_index_in_dim(batches, m_f, keepdims=False)
            # a microbatch enters the pipe at stage 0 chunk 0; every later
            # virtual stage consumes the ring message
            x = jnp.where((stage == 0) & (c_f == 0), feed, state)
            params_c = jax.tree.map(
                lambda leaf: lax.dynamic_index_in_dim(leaf, c_f, 0,
                                                      keepdims=False),
                params_all)
            # idle (fill/drain/pad) ticks skip the block compute (cond —
            # real per-device control flow inside shard_map) or run it
            # masked under PP x TP: see _unit_runner
            emitted, unit_aux = run_unit(active,
                                         lambda: unit_out(params_c, x),
                                         lambda: idle_out(x))
            if count > 1:
                permutation = [(source, (source + 1) % count)
                               for source in range(count)]
                state = lax.ppermute(emitted, STAGE, permutation)
            else:
                state = emitted
            return state, (emitted, unit_aux)

        if plan.path == 'overlap':
            # the skewed double-buffered schedule (pp='overlap', plain
            # GPipe only: chunks == 1 pinned by pp_plan). Stage s computes
            # microbatch m at tick m + 2s: the carry holds (last tick's
            # unsent output, the message received last tick), and the
            # send issues FIRST — this tick's compute consumes `arrived`,
            # not `incoming`, so the pp_hop transfer and the stage matmuls
            # are independent within one scan iteration and XLA's
            # latency-hiding scheduler runs them concurrently. The classic
            # tick's send sits between its producer and next tick's
            # consumer — unhideable inside a sequential scan.
            from tpusystem.parallel.collectives import pp_hop
            params_0 = jax.tree.map(lambda leaf: leaf[0], params_all)

            def overlap_tick(carry, t):
                pending, arrived = carry
                incoming = pp_hop(STAGE, plan.chunks, pending)
                unit = t - 2 * stage
                active = (unit >= 0) & (unit < microbatches)
                m = jnp.clip(unit, 0, microbatches - 1)
                feed = lax.dynamic_index_in_dim(batches, m, keepdims=False)
                x = jnp.where(stage == 0, feed, arrived)
                emitted, unit_aux = run_unit(active,
                                             lambda: unit_out(params_0, x),
                                             lambda: idle_out(x))
                # aux rides the scan ys, not the carry: a scalar carried
                # across the scan becomes a scalar shard_map residual at
                # linearization, which this jax's partial-eval cannot
                # name-check ({0: all_names} on a rank-0 aval)
                return (emitted, incoming), (emitted, unit_aux)

            ticks = microbatches + 2 * (count - 1)
            zero = jnp.zeros_like(batches[0])
            _, (emitted, aux_ticks) = lax.scan(overlap_tick, (zero, zero),
                                               jnp.arange(ticks))
            aux_local = jnp.sum(aux_ticks)
            # the last stage computes microbatch m at tick m + 2(S-1)
            emit_ticks = np.arange(microbatches) + 2 * (count - 1)
        else:
            ticks = chunks * padded + count - 1
            state = jnp.zeros_like(batches[0])
            _, (emitted, aux_ticks) = lax.scan(classic_tick, state,
                                               jnp.arange(ticks))
            aux_local = jnp.sum(aux_ticks)
            # the last stage emits microbatch m (final chunk) at tick
            # (m//S)*v*S + (v-1)*S + m%S + S-1 — contiguous [S-1, S-1+M)
            # for v == 1; gather the group-strided ticks otherwise
            emit_ticks = np.array(
                [(m // stages) * span + (chunks - 1) * stages + (m % stages)
                 + stages - 1 for m in range(microbatches)])
        outputs = jnp.take(emitted, emit_ticks, axis=0)
        outputs = _broadcast_from_last(outputs, stage, count)
        outputs = outputs.reshape(local_hidden.shape)
        if not has_aux:
            return outputs
        # aux: sum over stages (each unit lives on exactly one stage),
        # mean over every (unit, microbatch), mean over batch shards
        aux = lax.psum(aux_local, STAGE) / (microbatches * layers)
        if batch_axes:
            aux = lax.pmean(aux, batch_axes)
        return outputs, aux

    if _needs_jit_wrap(mesh):
        pipelined = jax.jit(pipelined)
    return pipelined(stacked_params, hidden)


def _broadcast_from_last(outputs, stage, count: int):
    """Ring-chain broadcast of the last stage's ``outputs`` to every stage:
    ``count - 1`` single-pair ``ppermute`` rounds walk the buffer around the
    ring one neighbor hop at a time. On the 1D ring ICI a stage axis maps to,
    each link carries the buffer exactly once (the zero-padded ring ``psum``
    this replaces moved ~2x the bytes per link to all-reduce mostly zeros);
    neighbor-only hops mean no multi-hop routing. Latency is count-1 hops —
    the same order as the ring all-reduce. A single-source multi-destination
    ``ppermute`` would be one hop but JAX requires unique destinations."""
    if count == 1:
        return outputs
    state = jnp.where(stage == count - 1, outputs, 0)
    for hop in range(count - 1):
        source = (count - 1 + hop) % count
        state = state + lax.ppermute(state, STAGE,
                                     [(source, (source + 1) % count)])
    return state


def _stage_scan(block_fn: BlockFn, has_aux: bool = False):
    """Apply this stage's local layer stack (leading dim layers/stages).

    With ``has_aux`` the block_fn returns ``(x, aux_scalar)`` per unit
    (MoE router losses) and the stage body returns ``(x, aux_sum)`` —
    the f32 sum over this stage's local units, reduced across stages and
    microbatches by the caller."""
    if has_aux:
        def run_aux(params, state):
            def layer(carry, layer_params):
                x, aux = carry
                x, unit_aux = block_fn(layer_params, x)
                return (x, aux + unit_aux.astype(jnp.float32)), None
            carry, _ = lax.scan(layer, (state, jnp.float32(0)), params)
            return carry
        return run_aux

    def run(params, state):
        def layer(carry, layer_params):
            return block_fn(layer_params, carry), None
        state, _ = lax.scan(layer, state, params)
        return state
    return run


@functools.lru_cache(maxsize=None)
def _stash_slots(stages: int, interleave: int, microbatches: int) -> int:
    """Smallest per-chunk stash size such that ``m % slots`` indexing never
    clobbers a live microbatch input.

    A chunk input written at its forward tick must survive until its
    backward tick; reuse of slot ``m % slots`` by microbatch ``m + slots``
    is safe iff that later forward happens strictly after this backward.
    Checked directly against the schedule formulas (see
    :func:`pipeline_train`); for ``interleave == 1`` this recovers the
    classic 1F1B bound ``2 * stages - 1``. Memoized: the brute-force
    check is O(slots * interleave * stages * microbatches) of pure Python
    and otherwise re-runs at every ``pipeline_train`` construction.
    """
    def fwd_tick(c, s, m):
        group, pos = divmod(m, stages)
        return s + group * interleave * stages + c * stages + pos

    def bwd_tick(c, s, m):
        group, pos = divmod(m, stages)
        return ((interleave * stages + stages - 2 - s)
                + group * interleave * stages
                + (interleave - 1 - c) * stages + pos)

    for slots in range(1, microbatches + 1):
        if all(fwd_tick(c, s, m + slots) > bwd_tick(c, s, m)
               for c in range(interleave) for s in range(stages)
               for m in range(microbatches - slots)):
            return slots
    return microbatches


def pipeline_train(head_fn, block_fn, tail_fn, mesh, *, microbatches: int,
                   weight_fn=None, interleave: int = 1):
    """1F1B-scheduled pipelined loss + gradients (one combined pass).

    The GPipe path (:func:`pipeline_apply` under ``jax.grad``) stashes
    O(microbatches) activations per stage because the backward replays the
    whole forward scan in reverse. This schedule interleaves forwards with
    backwards, so a microbatch's backward runs a bounded number of ticks
    after its forward and the per-stage stash is bounded independent of
    the microbatch count (block outputs are rematerialized in the backward
    ``jax.vjp``) — the activation-memory lever for deep pipes. The last
    stage backwards each microbatch in the same tick it forwards it
    (classic 1F1B).

    **Interleaved (circular) schedule** (``interleave = v > 1``): each
    device owns ``v`` *non-contiguous* layer chunks — virtual stage
    ``q = c * stages + s`` lives on device ``s`` — and microbatches travel
    the ring ``v`` times through chunk-sized units. With ``S`` stages and
    ``M`` microbatches the tick count is ``vM + vS + S - 2`` chunk-units
    against plain 1F1B's ``(M + 2S - 2)`` stage-units = ``v(M + 2S - 2)``
    chunk-units: the pipeline fill/drain bubble shrinks from ``~2S`` stage
    units toward ``~S/v`` stage units. Schedule (tick ``r``, device ``s``,
    groups of ``S`` microbatches per chunk sweep):

    * forward: unit index ``i = r - s`` (active while ``0 <= i < vM``),
      group ``g = i // (vS)``, chunk ``c_f = (i % (vS)) // S``, microbatch
      ``m_f = gS + i % S``.
    * backward: ``j = r - (vS + S - 2 - s)``, group ``g = j // (vS)``,
      chunk ``c_b = v - 1 - (j % (vS)) // S``, microbatch
      ``m_b = gS + j % S``.

    Every dependency (virtual stage ``q`` before ``q+1``, forward before
    backward, one-tick ``ppermute`` latency on both rings) holds with
    equality along the critical path, and for ``v = 1`` the formulas
    reduce exactly to classic 1F1B (forward ``r - s``, backward
    ``r - (2S - 2 - s)``).

    Idle units cost (almost) nothing *without tensor parallelism*: the
    head, the tail, and each block forward/backward unit sit under
    ``lax.cond`` — inside ``shard_map``, ``lax.cond`` on a device-varying
    predicate is real per-device control flow, so fill/drain ticks skip
    the block compute instead of executing it masked. With a live
    ``model`` axis (PP x TP) every unit runs *masked* instead — a
    GSPMD-inserted model collective cannot sit under control flow only
    some devices take — so block units pay the bubble's FLOPs and the
    head/tail run on every stage at every round (up to ~S x redundant
    head/tail work; keep the per-tick tail light under PP x TP — see
    :func:`_unit_runner`).

    No autodiff runs through the round loop: gradients are accumulated
    explicitly, so ``jax.grad`` of the caller is neither needed nor
    supported — the function *returns* the grads.

    Args:
        head_fn: ``(replicated_params, micro_inputs) -> activations`` —
            the pre-pipe part (embeddings), executed at stage 0 (chunk 0).
        block_fn: ``(layer_params, x) -> x`` per layer; layers stacked and
            stage-sharded as in :func:`pipeline_apply`.
        tail_fn: ``(replicated_params, activations, micro_targets) ->
            scalar mean loss`` — the post-pipe part (final norm, LM head,
            criterion), executed at the last stage (last chunk).
            ``replicated_params`` is ONE pytree shared by head and tail (a
            tied embedding appears in both; its two gradient contributions
            are summed).
        mesh: mesh with ``stage`` (and optionally data/fsdp) axes.
        microbatches: microbatches per step; batch must divide by
            ``data*fsdp*microbatches``. With interleave the schedule
            sweeps chunks in groups of ``stages`` microbatches; a
            remainder group is padded with idle units, so prefer
            ``microbatches % stages == 0``. The padding is the schedule's
            *intrinsic* short-group bubble, not an artifact: advancing a
            chunk sweep to the next chunk needs the previous chunk's
            output back from the last device — ``S`` one-tick ``ppermute``
            hops — and a group of ``R = M % S < S`` microbatches can only
            cover ``R`` of those ticks with work, so ``S - R`` idle units
            per chunk transition are forced by the ring latency (a
            "compressed" sweep would consume activations before they
            arrive). Total overhead: at most ``v * (S - R)`` idle
            chunk-units of ``vM + vS + S - 2`` — the same order as the
            fill/drain bubble itself, and second-order at realistic
            ``M >= 4S``.
        weight_fn: optional ``(micro_targets) -> scalar`` microbatch weight
            (the masked LM losses' unmasked-token count) — the same
            weighting ``build_train_step(accumulate=...)`` applies, so
            padded microbatches reproduce the full-batch mean. ``None``
            weighs microbatches equally.
        interleave: virtual-pipeline chunks per device. ``1`` = classic
            1F1B over contiguous stage slices (stacked leaves
            ``[layers, ...]``, sharded ``P(stage)``); ``v > 1`` expects
            stacked leaves reshaped to ``[v, layers/v, ...]`` (a plain
            reshape of the layer-major stack) sharded ``P(None, stage)``,
            so device ``s`` holds layers ``{(c*S + s) * Lc + j}``.

    Returns:
        ``step(replicated_params, stacked_params, inputs, targets) ->
        (loss, (d_replicated, d_stacked))`` with the loss and gradients
        weight-averaged over microbatches and data shards; gradients
        accumulate in float32 and return in the parameter dtypes.
    """
    stages = mesh.shape[STAGE]
    data_parallel = mesh.shape[DATA] * mesh.shape[FSDP]
    batch_axes = (DATA, FSDP) if data_parallel > 1 else None
    chunks = interleave
    slots = _stash_slots(stages, chunks, microbatches)
    # the interleaved schedule sweeps each chunk over groups of `stages`
    # microbatches; a partial last group is padded with idle units (clipped
    # microbatch indices would silently duplicate/skip work). For chunks==1
    # the group decomposition is exact for any microbatch count.
    padded = (microbatches if chunks == 1
              else -(-microbatches // stages) * stages)
    rounds = chunks * padded + chunks * stages + stages - 2
    stage_body = _stage_scan(block_fn)
    run_unit = _unit_runner(mesh)

    batch_spec = P(batch_axes)
    chunk_spec = P(STAGE) if chunks == 1 else P(None, STAGE)
    # the traced pipeline is memoized per stacked-params STRUCTURE (the
    # only input the shard_map specs depend on): an eager PP x TP caller
    # used to rebuild `run` and re-wrap it in a fresh `jax.jit` every
    # step, retracing the whole schedule each call — now the wrapper is
    # built once and jit's own cache handles shape changes
    runners: dict = {}

    def _build_runner(param_structure):
        param_specs = param_structure.unflatten(
            [chunk_spec] * param_structure.num_leaves)

        @functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=(P(), param_specs, batch_spec, batch_spec),
            out_specs=(P(), (P(), param_specs)),
            axis_names=_manual_axes(mesh))
        def run(reps, stacked, local_inputs, local_targets):
            stage = lax.axis_index(STAGE)
            count = stages
            micro = lambda a: a.reshape(
                (microbatches, a.shape[0] // microbatches) + a.shape[1:])
            micro_in, micro_tgt = micro(local_inputs), micro(local_targets)

            # unify layouts: local chunk stack [chunks, layers/chunk, ...]
            # (for chunks == 1 the P(stage) local slice [layers/S, ...]
            # gains a unit leading dim; grads reshape back at the end)
            stacked_in = stacked
            if chunks == 1:
                stacked = jax.tree.map(lambda leaf: leaf[None], stacked)

            def chunk_params(tree, c):
                if chunks == 1:
                    return jax.tree.map(lambda leaf: leaf[0], tree)
                return jax.tree.map(
                    lambda leaf: lax.dynamic_index_in_dim(leaf, c, 0,
                                                          keepdims=False),
                    tree)

            sample = head_fn(reps, micro_in[0])
            zero_act = jnp.zeros_like(sample)
            # gradient accumulators in float32 regardless of param dtype
            # (stable sums + exact token-count weights), cast back at the end
            zeros_f32 = lambda tree: jax.tree.map(
                lambda leaf: jnp.zeros(leaf.shape, jnp.float32), tree)
            carry = dict(
                fwd_msg=zero_act,
                bwd_msg=jnp.zeros_like(sample),
                stash=jnp.zeros((chunks, slots) + sample.shape, sample.dtype),
                d_stacked=zeros_f32(stacked),
                d_reps=zeros_f32(reps),
                loss=jnp.float32(0),
                weight=jnp.float32(0),
            )

            perm_fwd = [(i, (i + 1) % count) for i in range(count)]
            perm_bwd = [(i, (i - 1) % count) for i in range(count)]
            span = chunks * count    # ticks per (group, chunk) sweep

            def schedule(unit):
                """Unit index -> (active, chunk, microbatch)."""
                group, rem = jnp.divmod(unit, span)
                chunk, pos = jnp.divmod(rem, count)
                m = group * count + pos
                # padding units of a partial last group are idle, never
                # clipped onto a real microbatch (that would duplicate it)
                active = ((unit >= 0) & (unit < chunks * padded)
                          & (m < microbatches))
                return (active, jnp.clip(chunk, 0, chunks - 1),
                        jnp.clip(m, 0, microbatches - 1))

            def round_body(carry, r):
                active_f, c_f_raw, m_f = schedule(r - stage)
                c_f = c_f_raw
                feed = lax.dynamic_index_in_dim(micro_in, m_f, keepdims=False)
                # run_unit: lax.cond per-device control flow (only stage 0
                # pays for the embedding, only the last stage for the tail
                # fwd+bwd, fill/drain ticks skip the block unit) — or
                # masked lockstep execution under PP x TP (_unit_runner)
                x = run_unit((stage == 0) & (c_f == 0),
                             lambda: head_fn(reps, feed),
                             lambda: carry['fwd_msg'])
                params_f = chunk_params(stacked, c_f)
                y = run_unit(active_f,
                             lambda: stage_body(params_f, x),
                             lambda: zero_act)
                stash = jnp.where(
                    active_f,
                    lax.dynamic_update_slice(
                        carry['stash'], x[None, None],
                        (c_f, m_f % slots) + (0,) * x.ndim),
                    carry['stash'])

                # tail: the last stage turns its final-chunk forward into a
                # loss and a cotangent seed in the same tick (1F1B)
                tgt = lax.dynamic_index_in_dim(micro_tgt, m_f, keepdims=False)
                is_last = stage == count - 1
                active_t = active_f & is_last & (c_f == chunks - 1)

                def run_tail():
                    loss_m, (d_tail_m, dy) = jax.value_and_grad(
                        tail_fn, argnums=(0, 1))(reps, y, tgt)
                    return loss_m, d_tail_m, dy

                def skip_tail():
                    return (jnp.float32(0), jax.tree.map(jnp.zeros_like, reps),
                            jnp.zeros_like(y))

                loss_m, d_tail_m, dy = run_unit(active_t, run_tail,
                                                skip_tail)
                weight = (jnp.float32(weight_fn(tgt)) if weight_fn
                          else jnp.float32(1.0))
                # the weight rides the cotangent seed, so every downstream
                # gradient (blocks, head) is weighted without extra work
                dy = dy * weight.astype(dy.dtype)
                loss_acc = carry['loss'] + jnp.where(active_t,
                                                     loss_m * weight, 0)
                weight_acc = carry['weight'] + jnp.where(active_t, weight, 0)

                # backward unit: recompute this chunk's forward from the
                # stashed input (rematerialization) and pull grads through
                active_b, c_b_rev, m_b = schedule(
                    r - (chunks * count + count - 2 - stage))
                c_b = chunks - 1 - c_b_rev
                x_saved = lax.dynamic_slice(
                    stash, (c_b, m_b % slots) + (0,) * sample.ndim,
                    (1, 1) + sample.shape)
                x_saved = jnp.squeeze(x_saved, axis=(0, 1))
                # the last stage's final-chunk backward consumes the dy it
                # just produced; every other unit consumes the ring message
                cot = jnp.where(is_last & (c_b == chunks - 1), dy,
                                carry['bwd_msg'])
                params_b = chunk_params(stacked, c_b)

                def run_bwd():
                    _, vjp_fn = jax.vjp(stage_body, params_b, x_saved)
                    return vjp_fn(cot.astype(y.dtype))

                def skip_bwd():
                    return (jax.tree.map(jnp.zeros_like, params_b),
                            jnp.zeros_like(x_saved))

                d_chunk_m, dx = run_unit(active_b, run_bwd, skip_bwd)
                if chunks == 1:
                    d_stacked = jax.tree.map(
                        lambda acc, g: acc + g.astype(jnp.float32)[None],
                        carry['d_stacked'], d_chunk_m)
                else:
                    d_stacked = jax.tree.map(
                        lambda acc, g: lax.dynamic_update_index_in_dim(
                            acc,
                            lax.dynamic_index_in_dim(acc, c_b, 0,
                                                     keepdims=False)
                            + g.astype(jnp.float32),
                            c_b, 0),
                        carry['d_stacked'], d_chunk_m)

                # stage 0's chunk-0 input cotangent flows into the head
                feed_b = lax.dynamic_index_in_dim(micro_in, m_b,
                                                  keepdims=False)
                active_h = active_b & (stage == 0) & (c_b == 0)

                def run_head_vjp():
                    _, head_vjp = jax.vjp(lambda p: head_fn(p, feed_b), reps)
                    (d_head_m,) = head_vjp(dx)
                    return d_head_m

                d_head_m = run_unit(active_h, run_head_vjp,
                                    lambda: jax.tree.map(jnp.zeros_like, reps))
                accumulate = lambda acc_tree, grad_tree, condition: jax.tree.map(
                    lambda acc, g: acc + jnp.where(condition,
                                                   g.astype(jnp.float32), 0),
                    acc_tree, grad_tree)
                d_reps = accumulate(
                    accumulate(carry['d_reps'],
                               jax.tree.map(lambda g: g * weight, d_tail_m),
                               active_t),
                    d_head_m, active_h)

                return dict(
                    fwd_msg=lax.ppermute(y, STAGE, perm_fwd),
                    bwd_msg=lax.ppermute(dx, STAGE, perm_bwd),
                    stash=stash, d_stacked=d_stacked, d_reps=d_reps,
                    loss=loss_acc, weight=weight_acc), None

            if count > 1:
                carry, _ = lax.scan(round_body, carry, jnp.arange(rounds))
            else:
                # degenerate single stage: plain microbatch loop (head must
                # sit INSIDE the objective so embedding grads flow); the
                # chunk dim flattens back to the layer-major stack
                flat = jax.tree.map(
                    lambda leaf: leaf.reshape((-1,) + leaf.shape[2:]), stacked)

                def single(carry, m):
                    tgt = micro_tgt[m]
                    weight = (jnp.float32(weight_fn(tgt)) if weight_fn
                              else jnp.float32(1.0))

                    def objective(reps, flat):
                        x = head_fn(reps, micro_in[m])
                        return weight * tail_fn(reps, stage_body(flat, x),
                                                tgt)
                    loss_m, (d_r, d_s) = jax.value_and_grad(
                        objective, argnums=(0, 1))(reps, flat)
                    add_f32 = lambda acc_tree, grad_tree: jax.tree.map(
                        lambda acc, g: acc + g.astype(jnp.float32).reshape(
                            acc.shape),
                        acc_tree, grad_tree)
                    return dict(
                        carry,
                        loss=carry['loss'] + loss_m,
                        weight=carry['weight'] + weight,
                        d_reps=add_f32(carry['d_reps'], d_r),
                        d_stacked=add_f32(carry['d_stacked'], d_s),
                    ), None
                carry, _ = lax.scan(single, carry, jnp.arange(microbatches))

            # weighted means: sum(w_m * value_m) / sum(w_m) across the
            # microbatches of every data shard (loss/replicated grads also
            # sum over stage: each term lives on exactly one stage)
            batch_reduce = batch_axes or ()
            total = lax.psum(carry['weight'], (STAGE,) + batch_reduce)
            loss = lax.psum(carry['loss'], (STAGE,) + batch_reduce) / total
            d_reps = jax.tree.map(
                lambda g, p: (lax.psum(g, (STAGE,) + batch_reduce)
                              / total).astype(p.dtype),
                carry['d_reps'], reps)
            d_stacked = jax.tree.map(
                lambda g, p: (
                    (lax.psum(g, batch_reduce) if batch_reduce else g)
                    / total).astype(p.dtype).reshape(p.shape),
                carry['d_stacked'], stacked_in)
            return loss, (d_reps, d_stacked)

        return jax.jit(run) if _needs_jit_wrap(mesh) else run

    def step(replicated_params, stacked_params, inputs, targets):
        if inputs.shape[0] % (data_parallel * microbatches):
            raise ValueError(
                f'batch {inputs.shape[0]} not divisible by '
                f'data*fsdp*microbatches = {data_parallel}*{microbatches}')
        structure = jax.tree.structure(stacked_params)
        runner = runners.get(structure)
        if runner is None:
            runner = runners[structure] = _build_runner(structure)
        return runner(replicated_params, stacked_params, inputs, targets)

    return step


def PipelineParallel(stacked_prefix: str = r'(^|/)h/', extra_rules=(),
                     stacked_rules=(), fsdp: bool = False,
                     fsdp_min_size: int = 4096,
                     interleave: int = 1) -> ShardingPolicy:
    """Sharding policy for pipelined models: leaves under ``stacked_prefix``
    (the stacked layer collection) shard their leading ``layers`` dimension
    over ``stage``; everything else follows ``extra_rules`` / FSDP.

    ``stacked_rules`` composes Megatron TP *within* stages: ``(pattern,
    spec)`` pairs matched against the within-stack leaf path (the same
    per-block rules the non-pipelined family ships, e.g.
    ``('attn/qkv/kernel$', P(None, 'model'))``); the matched spec is
    shifted right past the stage dim(s), so a qkv kernel lands on
    ``P(stage, None, 'model')``. The pipeline's partial-manual
    ``shard_map`` leaves the ``model`` axis to GSPMD inside stage bodies,
    which turns these placements into partitioned stage matmuls + TP
    collectives (see the module docstring). Leaves no stacked rule
    matches fall back to plain stage sharding.

    ``interleave > 1`` matches :func:`pipeline_train`'s chunk-major layout
    (leaves ``[interleave, layers/interleave, ...]``): the *second* dim
    shards over ``stage``, so each device holds its ``interleave``
    non-contiguous chunks without per-step resharding."""
    rules = compose_stacked_rules(stacked_prefix, stacked_rules, interleave)
    rules += tuple(extra_rules)
    return ShardingPolicy(rules=rules, fsdp=fsdp, fsdp_min_size=fsdp_min_size)


def compose_stacked_rules(stacked_prefix: str, stacked_rules,
                          interleave: int = 1):
    """Shift within-stack TP rules past the stage dim(s) and append the
    plain stage-sharding fallback — the rule set both
    :func:`PipelineParallel` and the pipelined model families build their
    policies from. ``stacked_rules`` patterns are ``re.search``-ed against
    the leaf path, so anchor them to the leaf end (``kernel$``)."""
    stage_dims = (STAGE,) if interleave <= 1 else (None, STAGE)
    rules = tuple(
        (rf'(?:{stacked_prefix}).*(?:{pattern})', P(*stage_dims, *spec))
        for pattern, spec in stacked_rules)
    return rules + ((stacked_prefix, P(*stage_dims)),)
