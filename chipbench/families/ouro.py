"""The Ouro family: everything the benchmark knows about
``models/ouro.py::Ouro`` and the published configuration keys of
https://huggingface.co/ByteDance/Ouro-2.6B (``config.json``, ``model_type:
ouro``): a dense decoder whose stack of ``num_hidden_layers`` layers runs
``total_ut_steps`` times for every token, the same weights each pass.

The parts, under the names ``chipbench/README.md`` fixes for every family:
the program's module for serving (there is no training cell: the training
names raise); the seeded weights; the plain reference's reading
(``chipbench/reference/ouro.py``); the operation and byte counts. One
further name is this family's own reader's: ``loop_weight_ops_and_bytes``
(``readers/loop_roofline.py``).

The benchmark owns the weights. A leaf is ``N(0, 0.02)`` (norm weights that
plus one), drawn in float32 from a key of its own — the seed, the layer, the
leaf — and rounded to ``as_run.param_dtype``, as ``families/deepseek_v2.py``
draws them (whose draw this module shares). The reference is handed the same
draws widened to float32, one layer's when a pass asks for them.

**The counts count the stack ``total_ut_steps`` times a token.** That is
the published forward, not recomputation: every pass is a forward pass of
its own over its own keys (``flops.py``'s convention, which
``chipbench/README.md`` states for one pass a token). Matrix-product
parameters a token = ``total_ut_steps x num_hidden_layers x`` (the four
attention projections + the three of the gated MLP) + the untied head once
(its rows, the published vocabulary); embedding look-ups, norms and the
exit gate not counted. Causal attention adds ``2·(S/2)·heads·2·head_dim`` a
token a layer a pass at prefill, a decoded token at depth ``p``
``2·p·heads·2·head_dim`` a layer a pass: each pass attends its own keys.
"""

from __future__ import annotations

import sys

import jax.numpy as jnp
import numpy as np

from chipbench import check
from chipbench.families.deepseek_v2 import _group, norms, positions, vocab_size
from chipbench.reference import ouro as reference
from chipbench.weights import nest, seed_key

__all__ = ['norms', 'positions', 'vocab_size']


# ------------------------------------------------------------- the program

def serve_module(config: dict):
    """The module ``InferenceService`` is handed."""
    from tpusystem.models.ouro import Ouro
    c = config
    return Ouro(
        vocab_size=c['vocab_size'], layers=c['num_hidden_layers'],
        passes=c['total_ut_steps'], dim=c['hidden_size'],
        heads=c['num_attention_heads'], kv_heads=c['num_key_value_heads'],
        head_dim=c['head_dim'], ffn_dim=c['intermediate_size'],
        rope_theta=float(c['rope_theta']), eps=float(c['rms_norm_eps']),
        early_exit_threshold=float(c['early_exit_threshold']),
        max_seq=c['as_run']['max_seq'], dtype=c['as_run']['compute_dtype'])


def _no_training(*args, **kwargs):
    raise NotImplementedError(
        'the ouro family has no training cell: the loop is measured on the '
        'serving path, where its caches and its weight streams are')


def _no_such_kernel(*args, **kwargs):
    raise NotImplementedError(
        'the ouro family is served by the flax paged step: it runs neither '
        'the fused decode chain nor the flash kernel in a cell')


train_module = reference_training = train_ops_per_token = _no_training
flash_layers = flash_ops_and_bytes = _no_such_kernel
decode_chain_ops_and_bytes = _no_such_kernel


# ------------------------------------------------------ the seeded weights

def layer_leaves(config: dict, index: int) -> dict:
    """Reference name -> (the program's path under the layer, shape), in a
    fixed order (the order is part of the seeded draw; a path that ends in
    ``scale`` is a norm's weight, drawn plus one)."""
    del index                            # every layer has the same leaves
    c, d = config, config['hidden_size']
    wide = c['num_attention_heads'] * c['head_dim']
    narrow = c['num_key_value_heads'] * c['head_dim']
    ffn = c['intermediate_size']
    return {
        'input_norm': ('input_norm/scale', (d,)),
        'q': ('attn/q/kernel', (d, wide)),
        'k': ('attn/k/kernel', (d, narrow)),
        'v': ('attn/v/kernel', (d, narrow)),
        'o': ('attn/out/kernel', (wide, d)),
        'input_norm_2': ('input_norm_2/scale', (d,)),
        'post_attention_norm': ('post_attention_norm/scale', (d,)),
        'gate': ('gate/kernel', (d, ffn)),
        'up': ('up/kernel', (d, ffn)),
        'down': ('down/kernel', (ffn, d)),
        'post_attention_norm_2': ('post_attention_norm_2/scale', (d,))}


def top_leaves(config: dict) -> dict:
    d, rows = config['hidden_size'], config['vocab_size']
    return {'embedding': ('embedding', (rows, d)),
            'final_norm': ('final_norm/scale', (d,)),
            'lm_head': ('lm_head', (d, rows)),
            'exit_gate': ('exit_gate', (d,)),
            'exit_gate_bias': ('exit_gate_bias', ())}


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

def reference_model(config: dict) -> reference.Model:
    return reference.Model(
        num_hidden_layers=config['num_hidden_layers'],
        total_ut_steps=config['total_ut_steps'],
        num_attention_heads=config['num_attention_heads'],
        num_key_value_heads=config['num_key_value_heads'],
        head_dim=config['head_dim'], rope_theta=float(config['rope_theta']),
        rms_norm_eps=float(config['rms_norm_eps']))


def reference_leaves(config: dict, seed: int):
    """``leaves_of`` for the reference: the same draws as :func:`make`,
    widened to float32, one group at a time, drawn anew whenever asked."""
    key = seed_key(seed)

    def leaves_of(group):
        table = (top_leaves(config) if group == 'top'
                 else layer_leaves(config, group))
        return {name: leaf.astype(jnp.float32) for name, leaf
                in _group(config, key, group, table).items()}
    return leaves_of


def served_gap(config: dict, seed: int, sample: list,
               control_bits: int | None = None) -> tuple[float, int]:
    """The widest gap over ``sample`` and how many served tokens it covers:
    every one. With ``control_bits`` the reading is the control's instead,
    at the same positions."""
    padded = [check.sequence(prompt, tokens, positions(config))
              for prompt, tokens in sample]
    readings = reference.served_gaps(
        [jnp.asarray(ids) for ids, _ in padded],
        reference_leaves(config, seed), reference_model(config),
        control_bits=control_bits)
    gaps = np.concatenate([np.asarray(gaps)[span]
                           for gaps, (_, span) in zip(readings, padded)])
    whose = f' (control, {control_bits} bits)' if control_bits else ''
    print(f'served_gap{whose}: {gaps.size} served positions of '
          f'{len(sample)} requests; gaps p50 {np.median(gaps):.3g} p99 '
          f'{np.quantile(gaps, 0.99):.3g} '
          f'widest {np.sort(gaps)[-5:][::-1].round(3).tolist()}',
          file=sys.stderr)
    return float(gaps.max()), gaps.size


# ------------------------------------------- the operation and byte counts

def layer_params(config: dict) -> int:
    """One layer's matrices: the four attention projections and the three
    of the gated MLP."""
    c, d = config, config['hidden_size']
    wide = c['num_attention_heads'] * c['head_dim']
    narrow = c['num_key_value_heads'] * c['head_dim']
    return d * (2 * wide + 2 * narrow) + 3 * d * c['intermediate_size']


def loop_params(config: dict) -> int:
    """The matrices of every pass of every layer: what the loop streams a
    decode tick and what a token goes through."""
    return (config['total_ut_steps'] * config['num_hidden_layers']
            * layer_params(config))


def matmul_params(config: dict) -> int:
    return loop_params(config) + config['vocab_size'] * config['hidden_size']


def _attended(c: dict) -> int:
    """What one attended position costs: score and mix over ``head_dim``,
    every query head, every layer of every pass."""
    return (c['total_ut_steps'] * c['num_hidden_layers'] * 2
            * c['num_attention_heads'] * 2 * c['head_dim'])


def prefill_ops(config: dict, length: int) -> float:
    return length * (2 * matmul_params(config)
                     + (length / 2) * _attended(config))


def decode_ops(config: dict, depth: int) -> float:
    return 2 * matmul_params(config) + depth * _attended(config)


def kv_bytes_per_position(config: dict) -> float:
    """One cached position: a key and a value in every pass of every layer,
    in the pool's type."""
    c = config
    itemsize = jnp.dtype(c['as_run']['kv_cache_dtype']).itemsize
    return float(c['total_ut_steps'] * c['num_hidden_layers'] * 2
                 * c['num_key_value_heads'] * c['head_dim'] * itemsize)


def loop_weight_ops_and_bytes(config: dict, ticks: int,
                              tokens: int) -> tuple[float, float]:
    """The looped stack's matrix products over ``ticks`` decode ticks that
    decoded ``tokens`` tokens of seated rows: every tick streams every
    pass's matrices once, in the streamed type (the head is not in the
    loop); every token goes through them (2 operations a parameter)."""
    itemsize = jnp.dtype(config['as_run']['stream_dtype']).itemsize
    return (2.0 * tokens * loop_params(config),
            float(ticks) * itemsize * loop_params(config))
