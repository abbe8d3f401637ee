"""Model families: ``families/<family>.py``, found by a configuration's own
``family`` key. A family module is everything the benchmark knows about one
kind of model: how to build the program's module from the configuration's
published keys, the seeded weights, the plain reference's readings and the
operation and byte counts. ``chipbench/README.md`` fixes the names a family
module gives; nothing outside this directory names a model.
"""

import importlib

INTERFACE = (
    'train_module', 'serve_module', 'vocab_size', 'positions',
    'make', 'from_key', 'norms', 'reference_training', 'served_gap',
    'matmul_params', 'train_ops_per_token', 'prefill_ops', 'decode_ops',
    'flash_layers', 'flash_ops_and_bytes', 'decode_chain_ops_and_bytes',
    'kv_bytes_per_position')


def of(config: dict):
    """The module of ``config``'s family; one that lacks a name of the
    interface is refused here, before a run has spent anything on it."""
    module = importlib.import_module(
        f'chipbench.families.{config["family"]}')
    missing = [name for name in INTERFACE if not hasattr(module, name)]
    if missing:
        raise AttributeError(f'{module.__name__} does not give {missing}')
    return module
