"""The DeepSeek-V2 family: everything the benchmark knows about
``models/deepseek.py::DeepSeekV2`` and the published configuration keys of
https://huggingface.co/deepseek-ai/DeepSeek-V2 (``config.json``).

A configuration of this family is one chip's share of an expert-parallel
deployment (model-configs guide, section 4): ``num_hidden_layers``,
``n_routed_experts`` and ``vocab_size`` hold what is held *here* and
``published`` the source's values. The router keeps its published width
(``published.n_routed_experts``, whatever ``n_routed_experts`` holds), the
experts held are ``as_run.first_expert .. + n_routed_experts - 1``, and the
table and the head hold the first ``vocab_size`` rows.

The parts, under the names ``chipbench/README.md`` fixes for every family:
the program's module for serving (there is no training cell: the training
names raise); the seeded weights; the plain reference's reading
(``chipbench/reference/deepseek_v2.py`` does the arithmetic and imports
nothing of ``tpusystem/``); the operation and byte counts. Further names are
this family's own readers': ``held_experts``, ``latent_read_ops_and_bytes``,
``expert_ops_and_bytes``.

``served_gap`` compares every served position. bfloat16 flips a few per
cent of the expert choices against float32 (a near-tie of router scores),
and one flipped expert moves a logit by two of its standard deviations at
these seeded weights; so the reference goes through the experts the program
itself chose, which the engine hands out request by request
(``Engine(routing_sink=)``, :class:`Routed`), at weights from its own
scores. What is compared is then everything but the choice.

The benchmark owns the weights. Every leaf is ``N(0, 0.02)`` (norm weights
that plus one), drawn in float32 from a key of its own — the seed, the layer,
the leaf — and rounded to ``as_run.param_dtype``; the program's tree and the
reference's per-layer leaves come from the same draws, so neither takes
anything the other has made. Leaves are drawn one jitted call each: a whole
10 GB tree in one program would hold its float32 draws beside it.

The counts' convention (``flops.py``; fixed): matrix-product parameters a
token = the dense layer + each expert layer's MLA, shared experts, router and
the ``k x held / routed`` experts a token is expected to find here + the
head's rows held; embedding look-ups not counted. Causal attention adds
``2·(S/2)·heads·(nope + rope + v)`` a token a layer at prefill; a decoded
token at depth ``p`` attends the latent rows, ``2·p·heads·((rank + rope) +
rank)`` a layer, its two absorption products standing where the
up-projection's parameters are counted (the same ``rank x heads x (nope +
v)``).
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check
from chipbench.reference import deepseek_v2 as reference
from chipbench.weights import flatten, nest, seed_key

STD = 0.02


# ------------------------------------------------------------- the program

def router_width(config: dict) -> int:
    return config.get('published', {}).get('n_routed_experts',
                                           config['n_routed_experts'])


def held_experts(config: dict) -> tuple[int, int]:
    """``(first expert held, how many)``."""
    return config['as_run'].get('first_expert', 0), config['n_routed_experts']


class Routed(dict):
    """The experts the served program gave every position of every request
    it retired (``Engine(routing_sink=)``), by the request's prompt and
    tokens: ``[positions, expert layers, k]`` each."""

    @staticmethod
    def key(prompt, tokens) -> bytes:
        return (np.asarray(prompt, np.int32).tobytes() + b'|'
                + np.asarray(tokens, np.int32).tobytes())

    def record(self, tag, prompt, tokens, routing) -> None:
        del tag
        self[self.key(prompt, tokens)] = routing


def routed(config: dict) -> Routed | None:
    """The record :func:`serve_module` put among ``config``'s levers."""
    sink = config['as_run'].get('levers', {}).get('routing_sink')
    return getattr(sink, '__self__', None)


def serve_module(config: dict):
    """The module ``InferenceService`` is handed. The comparison goes
    through the experts the program chose (:func:`served_gap`), so the
    engine is asked for them: a fresh :class:`Routed` takes what it retires.
    The driver hands ``as_run.levers`` to ``InferenceService`` and nothing of
    the service back to the family, so the record travels among the levers
    (PERF.md section 7 asks the driver for a door of its own)."""
    from tpusystem.models.deepseek import DeepSeekV2
    rope, as_run = config['rope_scaling'], config['as_run']
    as_run.setdefault('levers', {})['routing_sink'] = Routed().record
    return DeepSeekV2(
        vocab_size=config['vocab_size'], layers=config['num_hidden_layers'],
        dim=config['hidden_size'], heads=config['num_attention_heads'],
        q_rank=config['q_lora_rank'], kv_rank=config['kv_lora_rank'],
        nope_dim=config['qk_nope_head_dim'],
        rope_dim=config['qk_rope_head_dim'], v_dim=config['v_head_dim'],
        dense_width=config['intermediate_size'],
        expert_width=config['moe_intermediate_size'],
        experts=router_width(config),
        experts_per_token=config['num_experts_per_tok'],
        expert_groups=config['n_group'], keep_groups=config['topk_group'],
        shared_experts=config['n_shared_experts'],
        routed_scale=float(config['routed_scaling_factor']),
        first_dense=config['first_k_dense_replace'],
        moe_every=config['moe_layer_freq'], held=held_experts(config),
        max_seq=as_run['max_seq'], eps=float(config['rms_norm_eps']),
        rope_theta=float(config['rope_theta']),
        rope_factor=float(rope['factor']),
        rope_original=rope['original_max_position_embeddings'],
        rope_beta_fast=float(rope['beta_fast']),
        rope_beta_slow=float(rope['beta_slow']),
        rope_mscale=float(rope['mscale']),
        rope_mscale_all_dim=float(rope['mscale_all_dim']),
        dtype=as_run['compute_dtype'])


def _no_training(*args, **kwargs):
    raise NotImplementedError(
        'the deepseek_v2 family has no training cell: at 16 bytes a '
        'parameter no cut within the floors fits a chip')


def _no_such_kernel(*args, **kwargs):
    raise NotImplementedError(
        'the deepseek_v2 family is served by the flax paged step: it runs '
        'neither the flash kernel nor the fused decode chain')


train_module = reference_training = train_ops_per_token = _no_training
flash_layers = flash_ops_and_bytes = _no_such_kernel
decode_chain_ops_and_bytes = _no_such_kernel


def vocab_size(config: dict) -> int:
    """Traffic draws its ids below this: the rows of the table held here."""
    return config['vocab_size']


def positions(config: dict) -> int:
    """How many positions a sequence may hold as the cell serves it."""
    return config['as_run']['max_seq']


def is_expert_layer(config: dict, index: int) -> bool:
    return (index >= config['first_k_dense_replace']
            and index % config['moe_layer_freq'] == 0)


# ------------------------------------------------------ the seeded weights

def _attention_leaves(c: dict) -> dict:
    """Reference name -> (the program's path under the layer, shape)."""
    d, heads = c['hidden_size'], c['num_attention_heads']
    q_head = c['qk_nope_head_dim'] + c['qk_rope_head_dim']
    kv_head = c['qk_nope_head_dim'] + c['v_head_dim']
    rank, q_rank = c['kv_lora_rank'], c['q_lora_rank']
    return {
        'attn_norm': ('attn_norm/scale', (d,)),
        'q_a': ('attn/q_a/kernel', (d, q_rank)),
        'q_norm': ('attn/q_norm/scale', (q_rank,)),
        'q_b': ('attn/q_b/kernel', (q_rank, heads * q_head)),
        'kv_a': ('attn/kv_a/kernel', (d, rank + c['qk_rope_head_dim'])),
        'kv_norm': ('attn/kv_norm/scale', (rank,)),
        'kv_b': ('attn/kv_b', (rank, heads * kv_head)),
        'out': ('attn/out/kernel', (heads * c['v_head_dim'], d)),
        'ffn_norm': ('ffn_norm/scale', (d,)),
    }


def layer_leaves(config: dict, index: int) -> dict:
    """The leaf table of layer ``index``, in a fixed order (the order is
    part of the seeded draw)."""
    d = config['hidden_size']
    table = _attention_leaves(config)
    if is_expert_layer(config, index):
        width, held = config['moe_intermediate_size'], held_experts(config)[1]
        shared = config['n_shared_experts'] * width
        table.update({
            'router': ('moe/router', (d, router_width(config))),
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


@functools.partial(jax.jit, static_argnames=('shape', 'plus_one', 'dtype'))
def _draw(key, *, shape, plus_one, dtype):
    leaf = STD * jax.random.normal(key, shape, jnp.float32)
    return (leaf + 1.0 if plus_one else leaf).astype(dtype)


def _group(config: dict, key, group, table: dict) -> dict:
    """``{reference name: leaf}`` of one group of leaves (``'top'`` or a
    layer's index), each from the key (seed, group, position in the table)."""
    which = 0 if group == 'top' else 1 + group
    dtype = jnp.dtype(config['as_run']['param_dtype'])
    return {name: _draw(jax.random.fold_in(jax.random.fold_in(key, which),
                                           position),
                        shape=shape, plus_one=path.endswith('scale'),
                        dtype=dtype)
            for position, (name, (path, shape)) in enumerate(table.items())}


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


def norms(tree: dict) -> dict:
    """Per-leaf L2 norms of a tree in the program's layout."""
    return {path: jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in flatten(tree).items()}


# ------------------------------------------------- the reference's readings

def reference_model(config: dict, *, held='as configured') -> reference.Model:
    rope = config['rope_scaling']
    return reference.Model(
        num_attention_heads=config['num_attention_heads'],
        q_lora_rank=config['q_lora_rank'],
        kv_lora_rank=config['kv_lora_rank'],
        qk_nope_head_dim=config['qk_nope_head_dim'],
        qk_rope_head_dim=config['qk_rope_head_dim'],
        v_head_dim=config['v_head_dim'], n_routed=router_width(config),
        num_experts_per_tok=config['num_experts_per_tok'],
        n_group=config['n_group'], topk_group=config['topk_group'],
        routed_scaling_factor=float(config['routed_scaling_factor']),
        rms_norm_eps=float(config['rms_norm_eps']),
        rope_theta=float(config['rope_theta']),
        rope_factor=float(rope['factor']),
        rope_original=rope['original_max_position_embeddings'],
        beta_fast=float(rope['beta_fast']), beta_slow=float(rope['beta_slow']),
        mscale=float(rope['mscale']),
        mscale_all_dim=float(rope['mscale_all_dim']),
        held=held_experts(config) if held == 'as configured' else held)


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


def served_gap(config: dict, seed: int, sample: list,
               control_bits: int | None = None) -> tuple[float, int]:
    """The widest gap over ``sample`` and how many served tokens it
    covers: every one. With ``control_bits`` the reading is the control's
    instead, at the same positions. The reference goes through the experts
    the program gave each position of each sampled request (prompt and
    served tokens alike: :class:`Routed`), so a choice of expert that
    flipped on a rounding is in neither reading; a configuration whose
    module was never built here (a reading of the reference against itself)
    takes the reference's own."""
    record = routed(config)
    # padded to the next of a few lengths, not to every sequence's own (a
    # compile each) nor all to the longest (three times the work)
    lengths = sorted({min(length, positions(config))
                      for length in (1024, 2048, 3072, 4096,
                                     positions(config))})
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
        reference_leaves(config, seed), config['num_hidden_layers'],
        reference_model(config), routing=routing, control_bits=control_bits)
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

def _mla_params(c: dict) -> int:
    d, heads = c['hidden_size'], c['num_attention_heads']
    return (d * c['q_lora_rank']
            + c['q_lora_rank'] * heads * (c['qk_nope_head_dim']
                                          + c['qk_rope_head_dim'])
            + d * (c['kv_lora_rank'] + c['qk_rope_head_dim'])
            + c['kv_lora_rank'] * heads * (c['qk_nope_head_dim']
                                           + c['v_head_dim'])
            + heads * c['v_head_dim'] * d)


def expert_params(c: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * c['hidden_size'] * c['moe_intermediate_size']


def matmul_params(config: dict) -> float:
    c, d = config, config['hidden_size']
    routed = router_width(c)
    here = c['num_experts_per_tok'] * held_experts(c)[1] / routed
    expert_layer = (_mla_params(c) + c['n_shared_experts'] * expert_params(c)
                    + d * routed + here * expert_params(c))
    dense_layer = _mla_params(c) + 3 * d * c['intermediate_size']
    layers = sum(expert_layer if is_expert_layer(c, index) else dense_layer
                 for index in range(c['num_hidden_layers']))
    return layers + c['vocab_size'] * d


def _attention_width(c: dict) -> int:
    return c['num_attention_heads'] * (c['qk_nope_head_dim']
                                       + c['qk_rope_head_dim']
                                       + c['v_head_dim'])


def _latent_width(c: dict) -> int:
    """What one attended latent row costs a head: the score over ``rank +
    rope`` and the mix over ``rank``."""
    return c['num_attention_heads'] * (2 * c['kv_lora_rank']
                                       + c['qk_rope_head_dim'])


def prefill_ops(config: dict, length: int) -> float:
    attention = (config['num_hidden_layers'] * 2 * (length / 2)
                 * _attention_width(config))
    return length * (2 * matmul_params(config) + attention)


def decode_ops(config: dict, depth: int) -> float:
    return (2 * matmul_params(config)
            + config['num_hidden_layers'] * 2 * depth * _latent_width(config))


def kv_bytes_per_position(config: dict) -> float:
    """One cached position: one latent row a layer, in the pool's type."""
    itemsize = jnp.dtype(config['as_run']['kv_cache_dtype']).itemsize
    return (config['num_hidden_layers']
            * (config['kv_lora_rank'] + config['qk_rope_head_dim'])
            * float(itemsize))


def latent_read_ops_and_bytes(config: dict,
                              attended: int) -> tuple[float, float]:
    """The decode steps' reads of the latent pool: ``attended`` is the
    positions attended (summed over the decoded tokens, each its row's
    depth); every layer reads each once."""
    return (attended * config['num_hidden_layers'] * 2.0
            * _latent_width(config),
            attended * kv_bytes_per_position(config))


def expert_ops_and_bytes(config: dict, hit: int,
                         seated: int) -> tuple[float, float]:
    """The grouped expert products of decode ticks: ``hit`` held experts
    had their three matrices streamed, ``seated`` assignments went through
    them (both summed over the expert layers and the ticks)."""
    itemsize = jnp.dtype(config['as_run']['stream_dtype']).itemsize
    return (seated * 2.0 * expert_params(config),
            hit * float(itemsize) * expert_params(config))
