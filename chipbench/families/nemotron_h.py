"""The Nemotron-H family: everything the benchmark knows about
``models/nemotron_h.py::NemotronH`` and the published configuration keys of
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
(``config.json``, ``model_type: nemotron_h``).

A configuration of this family is one chip's share of a deployment
(model-configs guide, section 4): ``num_hidden_layers`` and
``hybrid_override_pattern`` hold the layers kept, ``n_routed_experts`` and
``vocab_size`` what is held *here*, ``published`` the source's values. The
router keeps its published width, the experts held are
``as_run.first_expert .. + n_routed_experts - 1``, the table and the head
hold the first ``vocab_size`` rows: the same reading of those keys as
``families/deepseek_v2.py``'s, whose plain helpers for them (and its record
of the served routing, :class:`Routed`) this module shares.

The parts, under the names ``chipbench/README.md`` fixes for every family:
the program's module for serving (there is no training cell: the training
names raise); the seeded weights; the plain reference's reading
(``chipbench/reference/nemotron_h.py``); the operation and byte counts.
Further names are this family's own readers': ``expert_ops_and_bytes``,
``state_update_ops_and_bytes``, ``scan_ops_and_bytes``.

``served_gap`` compares every served position, the reference going through
the experts the program itself chose (``Engine(routing_sink=)``), as
``deepseek_v2``'s does and for its reason.

The benchmark owns the weights. A leaf is ``N(0, 0.02)`` (norm weights that
plus one; the skip ``D`` like any other leaf, so that the state's term and
not the skip's is most of ``y``: at ``D = 1`` four fifths of a layer's
output at these draws is the skip), drawn in float32 from a key of its own — the
seed, the layer, the leaf — and rounded to ``as_run.param_dtype``, but for
three leaves of a state-space layer, which at ``N(0, 0.02)`` would give a
recurrence that neither decays nor reads its input nor reaches the logits:
``A_log = ln U(1, 16)`` (so ``A`` in [-16, -1]), ``dt_bias`` the inverse
softplus of a step drawn log-uniform over ``[time_step_min, time_step_max]``
(the family's own initialisation of both), and the convolution's weights
``U(-K^-1/2, K^-1/2)`` (the framework default the family leaves them at:
at 0.02 the state's term in ``y`` is a thousandth of the skip's). An expert
layer's ``up`` and ``down`` are drawn at the shape the program stores them
in, both dimensions rounded up to multiples of ``as_run.expert_pad`` (2688 x
1856 -> 2816 x 2048: ``GatedExperts.pad_to``); the reference is handed the
``hidden_size x moe_intermediate_size`` corner, all that either reads.

The counts' convention (``flops.py``; fixed): matrix-product parameters a
token = each state-space layer's two projections, each attention layer's
four, each expert layer's router, shared expert and the ``k x held /
routed`` experts a token is expected to find here, + the head's rows held;
embedding look-ups, the convolution and vectors not counted. A state-space
layer adds ``4·H·P·N`` operations a token (the state's update and its
readout); causal attention ``2·(S/2)·heads·2·head_dim`` a token a layer at
prefill, a decoded token at depth ``p`` ``2·p·heads·2·head_dim``.
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check
from chipbench.families.deepseek_v2 import (Routed, held_experts, norms,
                                            positions, routed, router_width,
                                            vocab_size)
from chipbench.reference import nemotron_h as reference
from chipbench.weights import nest, seed_key

__all__ = ['Routed', 'held_experts', 'norms', 'positions', 'routed',
           'router_width', 'vocab_size']
STD = 0.02


# ------------------------------------------------------------- the program

def serve_module(config: dict):
    """The module ``InferenceService`` is handed; a fresh :class:`Routed`
    among the levers takes the routing of what the engine retires
    (``deepseek_v2.serve_module`` says why it travels there)."""
    from tpusystem.models.nemotron_h import NemotronH
    as_run = config['as_run']
    as_run.setdefault('levers', {})['routing_sink'] = Routed().record
    return NemotronH(
        vocab_size=config['vocab_size'],
        pattern=config['hybrid_override_pattern'], dim=config['hidden_size'],
        ssm_heads=config['mamba_num_heads'],
        ssm_head_dim=config['mamba_head_dim'], ssm_groups=config['n_groups'],
        ssm_state=config['ssm_state_size'], conv_kernel=config['conv_kernel'],
        chunk=config['chunk_size'], heads=config['num_attention_heads'],
        kv_heads=config['num_key_value_heads'], head_dim=config['head_dim'],
        expert_width=config['moe_intermediate_size'],
        shared_width=config['n_shared_experts']
        * config['moe_shared_expert_intermediate_size'],
        experts=router_width(config),
        experts_per_token=config['num_experts_per_tok'],
        routed_scale=float(config['routed_scaling_factor']),
        held=held_experts(config), max_seq=as_run['max_seq'],
        eps=float(config['layer_norm_epsilon']),
        expert_pad=as_run['expert_pad'], dtype=as_run['compute_dtype'])


def _no_training(*args, **kwargs):
    raise NotImplementedError(
        'the nemotron_h family has no training cell: the expert layer and '
        'the chunked scan have no backward pass here')


def _no_such_kernel(*args, **kwargs):
    raise NotImplementedError(
        'the nemotron_h family is served by the flax paged step: it runs '
        'neither the fused decode chain nor, by a count of its own, the '
        'flash kernel')


train_module = reference_training = train_ops_per_token = _no_training
flash_layers = flash_ops_and_bytes = _no_such_kernel
decode_chain_ops_and_bytes = _no_such_kernel


def kinds(config: dict) -> str:
    pattern = config['hybrid_override_pattern']
    assert len(pattern) == config['num_hidden_layers'], (
        pattern, config['num_hidden_layers'])
    return pattern


def _whole(c: dict, size: int) -> int:
    """``size`` rounded up to the multiple an expert's matrices are stored
    at (``as_run.expert_pad``)."""
    pad = c['as_run']['expert_pad']
    return -(-size // pad) * pad


def _sizes(c: dict) -> tuple:
    """``(inner, channels)`` of a state-space layer: heads x head_dim, and
    the convolution's width ``inner + 2·G·N``."""
    inner = c['mamba_num_heads'] * c['mamba_head_dim']
    return inner, inner + 2 * c['n_groups'] * c['ssm_state_size']


# ------------------------------------------------------ the seeded weights

def layer_leaves(config: dict, index: int) -> dict:
    """Reference name -> (the program's path under the layer, shape, how it
    is drawn), in a fixed order (the order is part of the seeded draw)."""
    c, d = config, config['hidden_size']
    kind = kinds(c)[index]
    table = {'norm': ('norm/scale', (d,), 'one')}
    if kind == 'M':
        inner, channels = _sizes(c)
        heads = c['mamba_num_heads']
        table.update({
            'in_proj': ('mixer/in_proj', (d, inner + channels + heads),
                        'normal'),
            'conv_weight': ('mixer/conv_weight', (channels, c['conv_kernel']),
                            'conv'),
            'conv_bias': ('mixer/conv_bias', (channels,), 'normal'),
            'A_log': ('mixer/A_log', (heads,), 'a_log'),
            'D': ('mixer/D', (heads,), 'normal'),
            'dt_bias': ('mixer/dt_bias', (heads,), 'dt_bias'),
            'norm_scale': ('mixer/norm_scale', (inner,), 'one'),
            'out_proj': ('mixer/out_proj', (inner, d), 'normal')})
    elif kind == '*':
        wide = c['num_attention_heads'] * c['head_dim']
        narrow = c['num_key_value_heads'] * c['head_dim']
        table.update({'q': ('mixer/q/kernel', (d, wide), 'normal'),
                      'k': ('mixer/k/kernel', (d, narrow), 'normal'),
                      'v': ('mixer/v/kernel', (d, narrow), 'normal'),
                      'out': ('mixer/out/kernel', (wide, d), 'normal')})
    else:
        width, held = c['moe_intermediate_size'], held_experts(c)[1]
        shared = c['n_shared_experts'] * c['moe_shared_expert_intermediate_size']
        table.update({
            'router': ('mixer/router', (d, router_width(c)), 'normal'),
            'correction': ('mixer/correction', (router_width(c),), 'normal'),
            'up': ('mixer/up', (held, _whole(c, d), _whole(c, width)),
                   'normal'),
            'down': ('mixer/down', (held, _whole(c, width), _whole(c, d)),
                     'normal'),
            'shared_up': ('mixer/shared_up/kernel', (d, shared), 'normal'),
            'shared_down': ('mixer/shared_down/kernel', (shared, d),
                            'normal')})
    return table


def top_leaves(config: dict) -> dict:
    d, rows = config['hidden_size'], config['vocab_size']
    return {'embedding': ('embedding', (rows, d), 'normal'),
            'final_norm': ('final_norm/scale', (d,), 'one'),
            'lm_head': ('lm_head', (d, rows), 'normal')}


@functools.partial(jax.jit, static_argnames=('shape', 'how', 'dtype', 'step'))
def _draw(key, *, shape, how, dtype, step=(0.001, 0.1)):
    if how == 'a_log':
        leaf = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif how == 'dt_bias':
        low, high = (math.log(edge) for edge in step)
        taken = jnp.exp(jax.random.uniform(key, shape, jnp.float32, low, high))
        leaf = taken + jnp.log(-jnp.expm1(-taken))     # softplus^-1(taken)
    elif how == 'conv':
        edge = shape[-1] ** -0.5
        leaf = jax.random.uniform(key, shape, jnp.float32, -edge, edge)
    else:
        leaf = STD * jax.random.normal(key, shape, jnp.float32)
        leaf = leaf + 1.0 if how == 'one' else leaf
    return leaf.astype(dtype)


def _group(config: dict, key, group, table: dict) -> dict:
    """``{reference name: leaf}`` of one group of leaves (``'top'`` or a
    layer's index), each from the key (seed, group, position in the table)."""
    which = 0 if group == 'top' else 1 + group
    dtype = jnp.dtype(config['as_run']['param_dtype'])
    step = (float(config['time_step_min']), float(config['time_step_max']))
    return {name: _draw(jax.random.fold_in(jax.random.fold_in(key, which),
                                           position),
                        shape=shape, how=how, dtype=dtype, step=step)
            for position, (name, (_, shape, how)) in enumerate(table.items())}


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


# ------------------------------------------------- the reference's readings

def reference_model(config: dict, *, held='as configured') -> reference.Model:
    return reference.Model(
        hybrid_override_pattern=kinds(config),
        mamba_num_heads=config['mamba_num_heads'],
        mamba_head_dim=config['mamba_head_dim'], n_groups=config['n_groups'],
        ssm_state_size=config['ssm_state_size'],
        conv_kernel=config['conv_kernel'],
        num_attention_heads=config['num_attention_heads'],
        num_key_value_heads=config['num_key_value_heads'],
        head_dim=config['head_dim'], n_routed=router_width(config),
        num_experts_per_tok=config['num_experts_per_tok'],
        routed_scaling_factor=float(config['routed_scaling_factor']),
        layer_norm_epsilon=float(config['layer_norm_epsilon']),
        held=held_experts(config) if held == 'as configured' else held)


def reference_leaves(config: dict, seed: int):
    """``leaves_of`` for the reference: the same draws as :func:`make`,
    widened to float32, one group at a time."""
    key = seed_key(seed)

    def leaves_of(group):
        table = (top_leaves(config) if group == 'top'
                 else layer_leaves(config, group))
        leaves = {name: leaf.astype(jnp.float32) for name, leaf
                  in _group(config, key, group, table).items()}
        if 'up' in leaves:       # the program stores them padded
            d, width = config['hidden_size'], config['moe_intermediate_size']
            leaves['up'] = leaves['up'][:, :d, :width]
            leaves['down'] = leaves['down'][:, :width, :d]
        return leaves
    return leaves_of


def served_gap(config: dict, seed: int, sample: list,
               control_bits: int | None = None) -> tuple[float, int]:
    """The widest gap over ``sample`` and how many served tokens it covers:
    every one. With ``control_bits`` the reading is the control's instead,
    at the same positions. The reference goes through the experts the
    program gave each position of each sampled request (:class:`Routed`); a
    configuration whose module was never built here takes the reference's
    own."""
    record = routed(config)
    # padded to the next of a few lengths (a compile each), whole query
    # blocks of the reference's attention
    lengths = sorted({min(length, positions(config))
                      for length in (512, 1024, positions(config))})
    padded = [check.sequence(prompt, tokens, next(
        length for length in lengths if length >= len(prompt) + len(tokens)))
        for prompt, tokens in sample]
    routing = None
    if record is not None:
        routing = []
        for (prompt, tokens), (ids, _) in zip(sample, padded):
            served = record[Routed.key(prompt, tokens)]
            assert served.shape[0] == len(prompt) + len(tokens) - 1, (
                served.shape, len(prompt), len(tokens))
            given = np.full((ids.shape[0],) + served.shape[1:], -1, np.int32)
            given[:served.shape[0]] = served
            routing.append(given)
    readings = reference.served_gaps(
        [jnp.asarray(ids) for ids, _ in padded],
        reference_leaves(config, seed), reference_model(config),
        routing=routing, control_bits=control_bits)
    gaps = np.concatenate([np.asarray(gaps)[span]
                           for (gaps, _), (_, span) in zip(readings, padded)])
    choices = sum(given[..., 0].size - int((given[..., 0] < 0).sum())
                  for given in routing or [])
    print(f'served_gap{f" (control, {control_bits} bits)" if control_bits else ""}'
          f': {gaps.size} served positions of {len(sample)} requests; the '
          f"program's experts were not the reference's own at "
          f'{sum(changed for _, changed in readings)} of {choices} choices; '
          f'gaps p50 {np.median(gaps):.3g} p99 {np.quantile(gaps, 0.99):.3g} '
          f'widest {np.sort(gaps)[-5:][::-1].round(3).tolist()}',
          file=sys.stderr)
    return float(gaps.max()), gaps.size


# ------------------------------------------- the operation and byte counts

def ssm_params(c: dict) -> int:
    """One state-space layer's two projections."""
    inner, channels = _sizes(c)
    return c['hidden_size'] * (inner + channels + c['mamba_num_heads']
                               + inner)


def attention_params(c: dict) -> int:
    wide = c['num_attention_heads'] * c['head_dim']
    narrow = c['num_key_value_heads'] * c['head_dim']
    return c['hidden_size'] * 2 * (wide + narrow)


def expert_params(c: dict) -> int:
    """One routed expert: up, down."""
    return 2 * c['hidden_size'] * c['moe_intermediate_size']


def state_ops(c: dict) -> int:
    """A token's state update and readout in one state-space layer."""
    return 4 * c['mamba_num_heads'] * c['mamba_head_dim'] * c['ssm_state_size']


def matmul_params(config: dict) -> float:
    c, d, pattern = config, config['hidden_size'], kinds(config)
    here = c['num_experts_per_tok'] * held_experts(c)[1] / router_width(c)
    shared = (c['n_shared_experts'] * 2 * d
              * c['moe_shared_expert_intermediate_size'])
    expert_layer = d * router_width(c) + shared + here * expert_params(c)
    return (pattern.count('M') * ssm_params(c)
            + pattern.count('*') * attention_params(c)
            + pattern.count('E') * expert_layer + c['vocab_size'] * d)


def _attended(c: dict) -> int:
    """What one attended position costs: score and mix over ``head_dim``,
    every query head, every attention layer."""
    return (kinds(c).count('*') * 2 * c['num_attention_heads']
            * 2 * c['head_dim'])


def prefill_ops(config: dict, length: int) -> float:
    return length * (2 * matmul_params(config)
                     + kinds(config).count('M') * state_ops(config)
                     + (length / 2) * _attended(config))


def decode_ops(config: dict, depth: int) -> float:
    return (2 * matmul_params(config)
            + kinds(config).count('M') * state_ops(config)
            + depth * _attended(config))


def kv_bytes_per_position(config: dict) -> float:
    """One cached position: a key and a value in each **attention** layer,
    in the pool's type (the state-space layers cache no position)."""
    itemsize = jnp.dtype(config['as_run']['kv_cache_dtype']).itemsize
    return (kinds(config).count('*') * 2 * config['num_key_value_heads']
            * config['head_dim'] * float(itemsize))


def state_bytes(config: dict) -> float:
    """What one row's cache holds in one state-space layer: the float32
    state and the convolution's last ``K - 1`` inputs."""
    c = config
    itemsize = jnp.dtype(c['as_run']['compute_dtype']).itemsize
    return (c['mamba_num_heads'] * c['mamba_head_dim'] * c['ssm_state_size']
            * 4.0 + (c['conv_kernel'] - 1) * _sizes(c)[1] * float(itemsize))


def state_update_ops_and_bytes(config: dict,
                               tokens: int) -> tuple[float, float]:
    """The decode steps' state updates: every one of ``tokens`` decoded
    tokens reads and writes its row's state once in every state-space
    layer."""
    layers = kinds(config).count('M')
    return (tokens * layers * float(state_ops(config)),
            tokens * layers * 2.0 * state_bytes(config))


def scan_ops_and_bytes(config: dict, tokens: int) -> tuple[float, float]:
    """The prefills' scans over ``tokens`` prompt tokens (true lengths): a
    token's update and readout by the recurrence's own count (the chunked
    form spends more to keep the matrix unit busy), and its ``x``, ``B``,
    ``C`` and ``dt`` read and ``y`` written once in every state-space
    layer."""
    c, layers = config, kinds(config).count('M')
    inner, channels = _sizes(c)
    itemsize = jnp.dtype(c['as_run']['compute_dtype']).itemsize
    moved = (channels * itemsize + c['mamba_num_heads'] * 4.0
             + inner * 4.0)
    return tokens * layers * float(state_ops(c)), tokens * layers * moved


def expert_ops_and_bytes(config: dict, hit: int,
                         seated: int) -> tuple[float, float]:
    """The grouped expert products of decode ticks: ``hit`` held experts
    had their two matrices streamed, ``seated`` assignments went through
    them (both summed over the expert layers and the ticks)."""
    itemsize = jnp.dtype(config['as_run']['stream_dtype']).itemsize
    return (seated * 2.0 * expert_params(config),
            hit * float(itemsize) * expert_params(config))
