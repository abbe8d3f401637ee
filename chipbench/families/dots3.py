"""The dots3 family: everything the benchmark knows about
``models/dots3.py::Dots3`` and the published configuration keys of
https://huggingface.co/dots-studio/dots3-note-prev (``config.json``,
``model_type: dots3_note``): latent attention of two kinds by
``layer_types`` (full layers with a learned sparse-attention indexer,
sliding layers of their own widths over a window), a headwise gate, and
sigmoid-routed experts with one shared.

A configuration of this family is one chip's share of an expert-parallel
deployment (model-configs guide, section 4), as in
``families/deepseek_v2.py``: ``num_hidden_layers`` (with ``layer_types``
its first entries), ``n_routed_experts`` and ``vocab_size`` hold what is
held *here* and ``published`` the source's values. The router keeps its
published width, the experts held are ``as_run.first_expert .. +
n_routed_experts - 1``, the table and the head hold the first
``vocab_size`` rows.

The parts, under the names ``chipbench/README.md`` fixes for every family:
the program's module for serving (there is no training cell: the training
names raise); the seeded weights (drawn as ``families/deepseek_v2.py``
draws them, whose draw this module shares); the plain reference's reading
(``chipbench/reference/dots3.py``), which goes through the experts and the
selections the program made (:func:`replay`) and makes its own windows; the
operation and byte counts. Further names are this family's readers':
``held_experts``, ``expert_ops_and_bytes`` (``readers/expert_roofline.py``)
and ``sparse_ops_and_bytes`` (``readers/sparse_roofline.py``); and
``selection_reading``, which ``drivers/serve_sparse.py`` compares beside
the logits.

The counts' convention (``flops.py``; fixed): matrix-product parameters a
token = every layer's attention (the indexer and the gate included), the
dense layer or each expert layer's shared expert, router and the ``k x
held / routed`` experts a token is expected to find here, and the head's
rows held; embedding look-ups not counted. Attention counts what the
algorithm attends: at prefill a full layer's query at ``t`` attends
``min(topk, t + 1)`` positions and its indexer scores ``t + 1``, a sliding
layer's attends ``min(window, t + 1)``; a decoded token at depth ``p``
scores ``p`` positions in each full layer and attends ``min(topk, p)``
there and ``min(window, p)`` in each sliding layer, through the absorbed
latent read (``heads · (2 · rank + rope)`` a position).
``kv_bytes_per_position`` is the paged part (each full layer's latent row
and indexer key); a sliding layer keeps ``window`` positions a row whatever
the context (:func:`window_bytes_per_row`).
"""

from __future__ import annotations

import sys

import jax.numpy as jnp
import numpy as np

from chipbench import check
from chipbench.families.deepseek_v2 import (Routed, _group, norms, positions,
                                            routed, vocab_size)
from chipbench.reference import dots3 as reference
from chipbench.weights import nest, seed_key

__all__ = ['norms', 'positions', 'vocab_size']

FULL = 'full_attention'
# the indexer's LayerNorm epsilon: DeepSeek-V3.2's; the published
# configuration states none. The program and the reference are handed it
# from here alone.
INDEX_NORM_EPS = 1e-6


# ------------------------------------------------------------- the program

def router_width(config: dict) -> int:
    return config.get('published', {}).get('n_routed_experts',
                                           config['n_routed_experts'])


def held_experts(config: dict) -> tuple[int, int]:
    """``(first expert held, how many)``."""
    return config['as_run'].get('first_expert', 0), config['n_routed_experts']


def layer_types(config: dict) -> tuple:
    return tuple(config['layer_types'][:config['num_hidden_layers']])


class Selected(Routed):
    """:class:`~chipbench.families.deepseek_v2.Routed`: the experts the
    served program gave every position of every request it retired;
    ``replayed``, the sample :func:`replay` served again and what it
    recorded then; ``reading``, what the last :func:`served_gap` read of
    the selections (:func:`selection_reading`)."""

    replayed: tuple | None = None
    reading: dict | None = None


def serve_module(config: dict):
    """The module ``InferenceService`` is handed. The comparison goes
    through the experts and the selections the program made
    (:func:`served_gap`), so the engine is asked for the experts: a
    :class:`Selected` takes what it retires, travelling among the levers as
    in ``families/deepseek_v2.py`` (made once a configuration: the module
    is made again for :func:`replay`)."""
    from tpusystem.models.dots3 import Dots3
    c, as_run = config, config['as_run']
    as_run.setdefault('levers', {}).setdefault('routing_sink',
                                               Selected().record)
    return Dots3(
        vocab_size=c['vocab_size'], layer_types=layer_types(c),
        dim=c['hidden_size'], heads=c['num_attention_heads'],
        q_rank=c['q_lora_rank'], kv_rank=c['kv_lora_rank'],
        nope_dim=c['qk_nope_head_dim'], rope_dim=c['qk_rope_head_dim'],
        v_dim=c['v_head_dim'], rope_theta=float(c['rope_theta']),
        index_heads=c['index_n_heads'], index_dim=c['index_head_dim'],
        index_topk=c['index_topk'], index_eps=INDEX_NORM_EPS,
        window_heads=c['swa_num_attention_heads'],
        window_q_rank=c['swa_q_lora_rank'],
        window_kv_rank=c['swa_kv_lora_rank'],
        window_nope_dim=c['swa_qk_nope_head_dim'],
        window_rope_dim=c['swa_qk_rope_head_dim'],
        window_v_dim=c['swa_v_head_dim'],
        window_theta=float(c['swa_rope_theta']),
        window=c['sliding_window_size'],
        lora_rescale=bool(c['apply_mla_qkv_lora_rescale']),
        dense_width=c['intermediate_size'],
        expert_width=c['moe_intermediate_size'], experts=router_width(c),
        experts_per_token=c['num_experts_per_tok'],
        shared_experts=c['n_shared_experts'],
        routed_scale=float(c['routed_scaling_factor']),
        first_dense=c['first_k_dense_replace'], held=held_experts(c),
        max_seq=as_run['max_seq'], eps=float(c['rms_norm_eps']),
        dtype=as_run['compute_dtype'])


def _no_training(*args, **kwargs):
    raise NotImplementedError(
        'the dots3 family has no training cell: at 16 bytes a parameter no '
        'cut within the floors fits a chip')


def _no_such_kernel(*args, **kwargs):
    raise NotImplementedError(
        'the dots3 family is served by the flax paged step: it runs neither '
        'the flash kernel nor the fused decode chain')


train_module = reference_training = train_ops_per_token = _no_training
flash_layers = flash_ops_and_bytes = _no_such_kernel
decode_chain_ops_and_bytes = _no_such_kernel


def is_expert_layer(config: dict, index: int) -> bool:
    return index >= config['first_k_dense_replace']


# ------------------------------------------------------ the seeded weights

def _kind(c: dict, kind: str) -> dict:
    """One kind of attention layer's sizes under plain names."""
    swa = '' if kind == FULL else 'swa_'
    heads = c[f'{swa}num_attention_heads']
    return {'heads': heads, 'q_rank': c[f'{swa}q_lora_rank'],
            'rank': c[f'{swa}kv_lora_rank'],
            'nope': c[f'{swa}qk_nope_head_dim'],
            'rope': c[f'{swa}qk_rope_head_dim'], 'v': c[f'{swa}v_head_dim']}


def _attention_leaves(c: dict, kind: str) -> dict:
    """Reference name -> (the program's path under the layer, shape)."""
    d, k = c['hidden_size'], _kind(c, kind)
    heads = k['heads']
    table = {
        'attn_norm': ('attn_norm/scale', (d,)),
        'q_a': ('attn/q_a/kernel', (d, k['q_rank'])),
        'q_norm': ('attn/q_norm/scale', (k['q_rank'],)),
        'q_b': ('attn/q_b/kernel', (k['q_rank'],
                                    heads * (k['nope'] + k['rope']))),
        'kv_a': ('attn/kv_a/kernel', (d, k['rank'] + k['rope'])),
        'kv_norm': ('attn/kv_norm/scale', (k['rank'],)),
        'kv_b': ('attn/kv_b', (k['rank'], heads * (k['nope'] + k['v']))),
        'out': ('attn/out/kernel', (heads * k['v'], d)),
        'attn_gate': ('attn/gate/kernel', (d, heads)),
    }
    if kind == FULL:
        index_heads, index_dim = c['index_n_heads'], c['index_head_dim']
        table.update({
            'index_q': ('attn/indexer/wq_b/kernel',
                        (k['q_rank'], index_heads * index_dim)),
            'index_k': ('attn/indexer/wk/kernel', (d, index_dim)),
            'index_k_norm': ('attn/indexer/k_norm/scale', (index_dim,)),
            'index_k_bias': ('attn/indexer/k_norm/bias', (index_dim,)),
            'index_weights': ('attn/indexer/weights_proj/kernel',
                              (d, index_heads)),
        })
    table['ffn_norm'] = ('ffn_norm/scale', (d,))
    return table


def layer_leaves(config: dict, index: int) -> dict:
    """The leaf table of layer ``index``, in a fixed order (the order is
    part of the seeded draw)."""
    d = config['hidden_size']
    table = _attention_leaves(config, layer_types(config)[index])
    if is_expert_layer(config, index):
        width, held = config['moe_intermediate_size'], held_experts(config)[1]
        shared = config['n_shared_experts'] * width
        table.update({
            'router': ('moe/router', (d, router_width(config))),
            'correction': ('moe/correction', (router_width(config),)),
            'gate': ('moe/gate', (held, d, width)),
            'up': ('moe/up', (held, d, width)),
            'down': ('moe/down', (held, width, d)),
            'shared_gate': ('moe/shared_gate/kernel', (d, shared)),
            'shared_up': ('moe/shared_up/kernel', (d, shared)),
            'shared_down': ('moe/shared_down/kernel', (shared, d)),
        })
    else:
        width = config['intermediate_size']
        table.update({'gate': ('gate/kernel', (d, width)),
                      'up': ('up/kernel', (d, width)),
                      'down': ('down/kernel', (width, d))})
    return table


def top_leaves(config: dict) -> dict:
    d, rows = config['hidden_size'], config['vocab_size']
    return {'embedding': ('embedding', (rows, d)),
            'final_norm': ('final_norm/scale', (d,)),
            'lm_head': ('lm_head', (d, rows))}


def from_key(config: dict, key) -> dict:
    """The program's tree for ``config`` from a key, in ``as_run.param_dtype``
    (traceable: the draws are jitted calls of their own)."""
    flat = {}
    table = top_leaves(config)
    for name, leaf in _group(config, key, 'top', table).items():
        flat[table[name][0]] = leaf
    for index in range(config['num_hidden_layers']):
        table = layer_leaves(config, index)
        for name, leaf in _group(config, key, index, table).items():
            flat[f'layer_{index}/{table[name][0]}'] = leaf
    return nest(flat)


def make(config: dict, seed: int) -> dict:
    """The program's tree for ``config`` from ``seed``, on the default
    device."""
    return from_key(config, seed_key(seed))


def parameters(config: dict) -> int:
    """Parameters held here: every leaf of the tree."""
    tables = [top_leaves(config)] + [layer_leaves(config, index) for index
                                     in range(config['num_hidden_layers'])]
    return sum(int(np.prod(shape)) for table in tables
               for _, shape in table.values())


# ------------------------------------------------- the reference's readings

def reference_model(config: dict, *, held='as configured') -> reference.Model:
    c = config

    def kind(name: str) -> reference.Attention:
        k = _kind(c, name)
        full = name == FULL
        return reference.Attention(
            heads=k['heads'], q_lora_rank=k['q_rank'],
            kv_lora_rank=k['rank'], qk_nope_head_dim=k['nope'],
            qk_rope_head_dim=k['rope'], v_head_dim=k['v'],
            rope_theta=float(c['rope_theta' if full else 'swa_rope_theta']),
            index=(c['index_n_heads'], c['index_head_dim'], c['index_topk'],
                   INDEX_NORM_EPS) if full else None,
            window=None if full else c['sliding_window_size'])

    return reference.Model(
        layer_types=layer_types(c), full=kind(FULL),
        sliding=kind('sliding_attention'), hidden_size=c['hidden_size'],
        n_routed=router_width(c),
        num_experts_per_tok=c['num_experts_per_tok'],
        routed_scaling_factor=float(c['routed_scaling_factor']),
        rms_norm_eps=float(c['rms_norm_eps']),
        lora_rescale=bool(c['apply_mla_qkv_lora_rescale']),
        held=held_experts(c) if held == 'as configured' else held)


def reference_leaves(config: dict, seed: int):
    """``leaves_of`` for the reference: the same draws as :func:`make`,
    widened to float32, one group at a time."""
    key = seed_key(seed)

    def leaves_of(group):
        table = (top_leaves(config) if group == 'top'
                 else layer_leaves(config, group))
        return {name: leaf.astype(jnp.float32) for name, leaf
                in _group(config, key, group, table).items()}
    return leaves_of


def replay(config: dict, seed: int, sample: list) -> dict:
    """Serve ``sample``'s requests again, after the timed window, and hand
    out their selections this time: ``{Routed.key: routing}`` as
    :func:`split_record` reads it, for each request the replay served
    token for token. A fresh ``InferenceService`` of the window's rows and
    block size (``as_run.service``, which the serving driver states) over
    the same module and weights runs the same programs, and a row's output
    does not depend on what is batched with it, so the replay makes the
    selections the window made; only the replay reads them off the device
    (``Engine(routing_selections=True)``), so the window times none of
    that reading."""
    import gc

    import jax
    from tpusystem.serve import InferenceService, Request
    again = Routed()
    levers = {**config['as_run']['levers'], 'routing_sink': again.record,
              'routing_selections': True}
    service = InferenceService(serve_module(config), make(config, seed),
                               **config['as_run']['service'], **levers)
    for index, (prompt, tokens) in enumerate(sample):
        service.service.handle('submit', Request(f'replay{index}',
                                                 list(prompt), len(tokens)))
    service.run_until_idle()
    service = None                 # free the program for the reference
    gc.collect()
    jax.clear_caches()
    return dict(again)


def served_gap(config: dict, seed: int, sample: list,
               control_bits: int | None = None) -> tuple[float, int]:
    """The widest gap over ``sample`` and how many served tokens it
    covers: every one. With ``control_bits`` the reading is the control's
    instead, at the same positions. The reference goes through the experts
    the program gave each position (``families/deepseek_v2.py::served_gap``
    says why) and through the positions its full layers selected, as
    :func:`replay` reads them, and makes its own windows. Where its own
    selection (or, with ``control_bits``, the control's) is not the
    program's it counts the positions: that reading is kept on the record
    for :func:`selection_reading`. A request the replay did not serve
    again token for token, with the window's experts, goes through the
    window's experts and the reference's own selections, and the reading
    is then not a number: what the served program selected is not
    known."""
    record = routed(config)
    # all padded to the longest a row holds: one compile of each kind of
    # layer, where a length each costs more in compiles than it saves
    length = positions(config)
    padded = [check.sequence(prompt, tokens, length)
              for prompt, tokens in sample]
    keys = [Routed.key(prompt, tokens) for prompt, tokens in sample]
    routing = selections = None
    unmatched = 0
    if record is not None:
        if record.replayed is None or record.replayed[0] != keys:
            record.replayed = (keys, replay(config, seed, sample))
        again = record.replayed[1]
        routing, selections = [], []
        for key, (prompt, tokens) in zip(keys, sample):
            served = record[key]
            assert served.shape[0] == len(prompt) + len(tokens) - 1, (
                served.shape, len(prompt), len(tokens))
            experts = np.full((length, *served.shape[1:]), -1, np.int32)
            experts[:served.shape[0]] = served
            selected = None
            if key in again:
                given = np.full((length, again[key].shape[1]), -1, np.int32)
                given[:served.shape[0]] = again[key]
                replayed, selected = split_record(config, given)
                if not np.array_equal(replayed, experts):
                    selected = None
            unmatched += selected is None
            routing.append(experts)
            selections.append(selected)
    readings = reference.served_gaps(
        [jnp.asarray(ids) for ids, _ in padded],
        reference_leaves(config, seed), reference_model(config),
        routing=routing, selections=selections, control_bits=control_bits)
    gaps = np.concatenate([np.asarray(gaps)[span]
                           for (gaps, _, _), (_, span)
                           in zip(readings, padded)])
    choices = sum(experts[..., 0].size - int((experts[..., 0] < 0).sum())
                  for experts in routing or [])
    flips = sum(flipped for _, _, flipped in readings)
    selected = selected_positions(config, sample) if record else 0
    if record is not None:
        record.reading = {
            'flips': flips, 'selected': selected, 'unmatched': unmatched,
            'share': float('nan') if unmatched else flips / max(selected, 1),
            'control_bits': control_bits}
    control = f' (control, {control_bits} bits)' if control_bits else ''
    print(f'served_gap{control}: {gaps.size} served positions of '
          f'{len(sample)} requests ({unmatched} not replayed token for '
          f"token); the program's experts were not the reference's own at "
          f'{sum(changed for _, changed, _ in readings)} of {choices} '
          f"choices; its selections were not the reference's own at "
          f'{flips} of {selected} selected positions '
          f'({100 * flips / max(selected, 1):.3f} %); gaps p50 '
          f'{np.median(gaps):.3g} p99 {np.quantile(gaps, 0.99):.3g} widest '
          f'{np.sort(gaps)[-5:][::-1].round(3).tolist()}', file=sys.stderr)
    return float(gaps.max()), gaps.size


def selected_positions(config: dict, sample: list) -> int:
    """How many positions the full layers select over ``sample``'s
    positions that the record covers (every position but each sequence's
    last): ``min(topk, t + 1)`` at position ``t``, in each full layer."""
    return _counts(config)[0] * sum(
        _attended_sum(len(prompt) + len(tokens) - 1, config['index_topk'])
        for prompt, tokens in sample)


def selection_reading(config: dict) -> dict | None:
    """What the last :func:`served_gap` read of the selections: ``flips``,
    the positions where the program's selection and the reference's own
    (the control's, where ``control_bits``) differ, either way, over the
    compared positions of every full layer; ``selected``, the positions the
    reference selects there (:func:`selected_positions`); ``share``, the
    one over the other (not a number where ``unmatched`` requests were not
    replayed token for token). ``None`` where no comparison went through
    the program's selections."""
    record = routed(config)
    return getattr(record, 'reading', None)


def split_record(config: dict, given: np.ndarray):
    """What the engine hands a ``routing_sink`` asked for the selections
    (``routing_selections``) for this family, ``[positions, expert layers
    * k + full layers * max_seq / 32]`` (the experts chosen, then each
    full layer's selection as words), as
    ``(experts [positions, expert layers, k], selections [positions, full
    layers, max_seq / 32])``; -1 stays -1 (nothing served)."""
    k = config['num_experts_per_tok']
    experts = sum(is_expert_layer(config, index)
                  for index in range(config['num_hidden_layers']))
    words = positions(config) // 32
    return (given[:, :experts * k].reshape(-1, experts, k),
            given[:, experts * k:].reshape(-1, _counts(config)[0], words))


# ------------------------------------------- the operation and byte counts

def attention_params(c: dict, kind: str) -> int:
    """One attention layer's matrix-product parameters (the gate and, in a
    full layer, the indexer included)."""
    return sum(int(np.prod(shape)) for name, (_, shape)
               in _attention_leaves(c, kind).items()
               if len(shape) == 2)


def expert_params(c: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * c['hidden_size'] * c['moe_intermediate_size']


def matmul_params(config: dict) -> float:
    c, d = config, config['hidden_size']
    routed_width = router_width(c)
    here = c['num_experts_per_tok'] * held_experts(c)[1] / routed_width
    total = c['vocab_size'] * d
    for index, kind in enumerate(layer_types(c)):
        total += attention_params(c, kind)
        if is_expert_layer(c, index):
            total += (c['n_shared_experts'] * expert_params(c)
                      + d * routed_width + here * expert_params(c))
        else:
            total += 3 * d * c['intermediate_size']
    return total


def _latent_cost(c: dict, kind: str) -> int:
    """Operations of one attended position through the absorbed latent
    read, a layer: the score over ``rank + rope`` and the mix over
    ``rank``, two each a head."""
    k = _kind(c, kind)
    return 2 * k['heads'] * (2 * k['rank'] + k['rope'])


def _index_cost(c: dict) -> int:
    """Operations of one scored position in one full layer's indexer."""
    return 2 * c['index_n_heads'] * c['index_head_dim']


def _counts(c: dict) -> tuple[int, int]:
    kinds = layer_types(c)
    full = sum(kind == FULL for kind in kinds)
    return full, len(kinds) - full


def _attended_sum(length: int, reach: int) -> int:
    """``sum_{t < length} min(reach, t + 1)``."""
    inside = min(length, reach)
    return inside * (inside + 1) // 2 + (length - inside) * reach


def prefill_ops(config: dict, length: int) -> float:
    c = config
    full, sliding = _counts(c)
    expanded = lambda kind: 2 * _kind(c, kind)['heads'] * (
        _kind(c, kind)['nope'] + _kind(c, kind)['rope'] + _kind(c, kind)['v'])
    attention = (full * (_attended_sum(length, c['index_topk'])
                         * expanded(FULL)
                         + _attended_sum(length, length) * _index_cost(c))
                 + sliding * _attended_sum(length, c['sliding_window_size'])
                 * expanded('sliding_attention'))
    return length * 2 * matmul_params(c) + attention


def decode_ops(config: dict, depth: int) -> float:
    c = config
    full, sliding = _counts(c)
    return (2 * matmul_params(c)
            + full * (depth * _index_cost(c) + min(depth, c['index_topk'])
                      * _latent_cost(c, FULL))
            + sliding * min(depth, c['sliding_window_size'])
            * _latent_cost(c, 'sliding_attention'))


def _itemsize(config: dict) -> int:
    return jnp.dtype(config['as_run']['kv_cache_dtype']).itemsize


def _row_bytes(c: dict, kind: str) -> int:
    """One latent row as counted (``rank + rope`` values, not its lanes)."""
    k = _kind(c, kind)
    return (k['rank'] + k['rope']) * _itemsize(c)


def kv_bytes_per_position(config: dict) -> float:
    """One cached position of the paged pool: each full layer's latent row
    and indexer key, in the pool's type."""
    full, _ = _counts(config)
    return float(full * (_row_bytes(config, FULL)
                         + config['index_head_dim'] * _itemsize(config)))


def window_bytes_per_row(config: dict) -> float:
    """What a row keeps of the sliding layers whatever its context: the
    window's latent rows in each."""
    _, sliding = _counts(config)
    return float(sliding * config['sliding_window_size']
                 * _row_bytes(config, 'sliding_attention'))


def sparse_ops_and_bytes(config: dict, part: str,
                         depths: list) -> tuple[float, float]:
    """The decode steps' reads of one part of the attention, summed over
    the decoded tokens (``depths``: each token's row depth, this step's
    position included): ``'indexer'`` every held position's key scored in
    each full layer; ``'sparse_read'`` the ``min(depth, topk)`` selected
    latent rows attended in each full layer; ``'window_read'`` the
    ``min(depth, window)`` rows of each sliding layer."""
    c = config
    full, sliding = _counts(c)
    if part == 'indexer':
        positions_read = full * sum(depths)
        return (positions_read * float(_index_cost(c)),
                positions_read * float(c['index_head_dim'] * _itemsize(c)))
    if part == 'sparse_read':
        positions_read = full * sum(min(d, c['index_topk']) for d in depths)
        return (positions_read * float(_latent_cost(c, FULL)),
                positions_read * float(_row_bytes(c, FULL)))
    if part == 'window_read':
        kind = 'sliding_attention'
        positions_read = sliding * sum(min(d, c['sliding_window_size'])
                                       for d in depths)
        return (positions_read * float(_latent_cost(c, kind)),
                positions_read * float(_row_bytes(c, kind)))
    raise ValueError(f'unknown part {part!r}')


def expert_ops_and_bytes(config: dict, hit: int,
                         seated: int) -> tuple[float, float]:
    """The grouped expert products of decode ticks: ``hit`` held experts
    had their three matrices streamed, ``seated`` assignments went through
    them (both summed over the expert layers and the ticks)."""
    itemsize = jnp.dtype(config['as_run']['stream_dtype']).itemsize
    return (seated * 2.0 * expert_params(config),
            hit * float(itemsize) * expert_params(config))
