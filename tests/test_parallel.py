"""Mesh + sharding-policy tests on 8 simulated devices — the multi-device
coverage the reference lacks entirely (SURVEY.md §4 implications)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpusystem.models import MLP
from tpusystem.parallel import (
    DATA, FSDP, MODEL, DataParallel, FullyShardedDataParallel, MeshSpec,
    ShardingPolicy, TensorParallel, batch_sharding, single_device_mesh,
)
from tpusystem.train import Adam, CrossEntropyLoss, build_train_step, flax_apply, init_state


def test_mesh_spec_wildcard_resolution():
    spec = MeshSpec(data=-1, model=2)
    sizes = spec.resolved_sizes(8)
    assert sizes['data'] == 4 and sizes['model'] == 2
    mesh = spec.build()
    assert mesh.shape['data'] == 4 and mesh.shape['model'] == 2
    assert mesh.shape['fsdp'] == 1


def test_mesh_spec_errors():
    with pytest.raises(ValueError, match='only one axis'):
        MeshSpec(data=-1, model=-1).resolved_sizes(8)
    with pytest.raises(ValueError, match='not divisible'):
        MeshSpec(data=-1, model=3).resolved_sizes(8)
    with pytest.raises(ValueError, match='wants'):
        MeshSpec(data=4).build()


def test_mesh_spec_identity_distinguishes_layouts():
    from tpusystem.registry import gethash
    assert gethash(MeshSpec(data=4, model=2)) != gethash(MeshSpec(data=2, model=4))


def test_single_device_mesh_works():
    mesh = single_device_mesh()
    assert mesh.devices.size == 1


def test_fsdp_policy_shards_largest_divisible_dim():
    mesh = MeshSpec(fsdp=-1).build()  # fsdp=8
    policy = FullyShardedDataParallel(min_size=16)
    params = {'dense': {'kernel': jnp.zeros((24, 64)), 'bias': jnp.zeros((64,))},
              'tiny': jnp.zeros((2, 2))}
    specs = policy.tree_specs(params, mesh)
    assert specs['dense']['kernel'] == P(None, 'fsdp')  # 64 > 24
    assert specs['dense']['bias'] == P('fsdp')
    assert specs['tiny'] == P()  # below min_size


def test_tensor_parallel_rules_with_fsdp_fallback():
    mesh = MeshSpec(fsdp=2, model=4).build()
    policy = TensorParallel(
        rules=[(r'attention/query/kernel$', P(None, 'model')),
               (r'mlp/out/kernel$', P('model', None))],
        fsdp=True, fsdp_min_size=16)
    params = {
        'attention': {'query': {'kernel': jnp.zeros((16, 32))}},
        'mlp': {'out': {'kernel': jnp.zeros((32, 16))}},
        'embed': {'kernel': jnp.zeros((64, 8))},
    }
    specs = policy.tree_specs(params, mesh)
    assert specs['attention']['query']['kernel'] == P('fsdp', 'model')
    assert specs['mlp']['out']['kernel'] == P('model', 'fsdp')
    assert specs['embed']['kernel'] == P('fsdp')


def test_rule_axis_dropped_when_not_divisible():
    mesh = MeshSpec(model=8).build()
    policy = ShardingPolicy(rules=[(r'kernel$', P(None, 'model'))])
    specs = policy.tree_specs({'kernel': jnp.zeros((4, 6))}, mesh)  # 6 % 8 != 0
    assert specs['kernel'] == P()


def test_optimizer_state_inherits_param_rules():
    """Adam mu/nu paths end with the parameter path, so TP rules cover them."""
    mesh = MeshSpec(data=-1, model=2).build()
    policy = TensorParallel(rules=[(r'Dense_\d+/kernel$', P(None, 'model'))])
    module = MLP(features=(32,), classes=8)
    optimizer = Adam()
    state = init_state(module, optimizer, jnp.zeros((4, 28, 28)))
    specs = policy.tree_specs(state, mesh)
    kernel_spec = specs.params['Dense_0']['kernel']
    # all Dense kernels match the rule
    assert kernel_spec == P(None, 'model')
    mu_specs = jax.tree.leaves(
        specs.opt_state, is_leaf=lambda leaf: isinstance(leaf, P))
    assert P(None, 'model') in mu_specs


@pytest.fixture(scope='module')
def digits_batch():
    rng = np.random.default_rng(0)
    inputs = rng.normal(size=(64, 28, 28)).astype(np.float32)
    targets = rng.integers(0, 10, size=(64,)).astype(np.int32)
    return jnp.asarray(inputs), jnp.asarray(targets)


def _train_losses(mesh, policy, batch, steps=4):
    module = MLP(features=(64,), classes=10, dropout=0.0)
    optimizer = Adam(lr=1e-2)
    state = init_state(module, optimizer, jnp.zeros((8, 28, 28)), rng=0)
    state = policy.place(state, mesh)
    inputs = jax.device_put(batch[0], batch_sharding(mesh))
    targets = jax.device_put(batch[1], batch_sharding(mesh))
    step = build_train_step(flax_apply(module), CrossEntropyLoss(), optimizer)
    losses = []
    for _ in range(steps):
        state, (_, loss) = step(state, inputs, targets)
        losses.append(float(loss))
    return losses, state


@pytest.mark.slow
def test_dp_matches_single_device_numerics(digits_batch):
    single_losses, _ = _train_losses(single_device_mesh(), DataParallel(), digits_batch)
    mesh = MeshSpec(data=-1).build()
    dp_losses, state = _train_losses(mesh, DataParallel(), digits_batch)
    np.testing.assert_allclose(single_losses, dp_losses, rtol=2e-5)


def test_fsdp_matches_single_device_and_actually_shards(digits_batch):
    single_losses, _ = _train_losses(single_device_mesh(), DataParallel(), digits_batch)
    mesh = MeshSpec(fsdp=-1).build()
    fsdp_losses, state = _train_losses(mesh, FullyShardedDataParallel(min_size=64), digits_batch)
    np.testing.assert_allclose(single_losses, fsdp_losses, rtol=2e-5)
    kernel = state.params['Dense_0']['kernel']  # (784, 64) -> sharded on dim 0
    shard_shape = kernel.addressable_shards[0].data.shape
    assert shard_shape[0] == kernel.shape[0] // 8, shard_shape


def test_tp_matches_single_device_and_shards_kernels(digits_batch):
    single_losses, _ = _train_losses(single_device_mesh(), DataParallel(), digits_batch)
    mesh = MeshSpec(model=-1).build()
    policy = TensorParallel(rules=[
        (r'Dense_0/kernel$', P(None, 'model')),
        (r'Dense_1/kernel$', P('model', None)),
    ])
    tp_losses, state = _train_losses(mesh, policy, digits_batch)
    np.testing.assert_allclose(single_losses, tp_losses, rtol=2e-5)
    kernel = state.params['Dense_0']['kernel']
    assert kernel.addressable_shards[0].data.shape[1] == kernel.shape[1] // 8


def test_combined_dp_fsdp_tp_mesh(digits_batch):
    """2-axis data x 2 fsdp x 2 model — the full combined layout compiles
    and trains with identical numerics."""
    single_losses, _ = _train_losses(single_device_mesh(), DataParallel(), digits_batch)
    mesh = MeshSpec(data=2, fsdp=2, model=2).build()
    policy = TensorParallel(
        rules=[(r'Dense_0/kernel$', P(None, 'model'))], fsdp=True, fsdp_min_size=64)
    combined_losses, _ = _train_losses(mesh, policy, digits_batch)
    np.testing.assert_allclose(single_losses, combined_losses, rtol=2e-5)


class TestCollectiveVocabulary:
    """The shard_map collective wrappers — the data-plane vocabulary every
    explicit kernel (ring attention, pipeline, MoE) builds on."""

    def _mapped(self, fn, n=4, out_spec=None):
        import jax
        from jax.sharding import PartitionSpec as P
        from tpusystem.parallel import MeshSpec
        mesh = MeshSpec(data=n).build(jax.devices()[:n])
        return jax.shard_map(
            fn, mesh=mesh, in_specs=P('data'),
            out_specs=P('data') if out_spec is None else out_spec)

    def test_reductions_and_gather(self):
        import jax.numpy as jnp
        import numpy as np
        from tpusystem.parallel import (all_gather, all_reduce_mean,
                                        all_reduce_sum)
        values = jnp.arange(4.0)

        total = self._mapped(lambda x: all_reduce_sum(x, 'data'))(values)
        np.testing.assert_array_equal(np.asarray(total), [6.0] * 4)
        mean = self._mapped(lambda x: all_reduce_mean(x, 'data'))(values)
        np.testing.assert_array_equal(np.asarray(mean), [1.5] * 4)
        gathered = self._mapped(lambda x: all_gather(x, 'data'))(values)
        # every shard holds the full gathered array
        np.testing.assert_array_equal(np.asarray(gathered),
                                      list(range(4)) * 4)

    def test_reduce_scatter_and_ring_shift(self):
        import jax.numpy as jnp
        import numpy as np
        from tpusystem.parallel import reduce_scatter, ring_shift

        scattered = self._mapped(
            lambda x: reduce_scatter(x[0], 'data'))(jnp.ones((4, 4)))
        np.testing.assert_array_equal(np.asarray(scattered), [4.0] * 4)

        shifted = self._mapped(lambda x: ring_shift(x, 'data'))(jnp.arange(4.0))
        np.testing.assert_array_equal(np.asarray(shifted), [3, 0, 1, 2])
        back = self._mapped(
            lambda x: ring_shift(x, 'data', reverse=True))(jnp.arange(4.0))
        np.testing.assert_array_equal(np.asarray(back), [1, 2, 3, 0])

    def test_all_to_all_shard_transpose(self):
        import jax.numpy as jnp
        import numpy as np
        from tpusystem.parallel import all_to_all

        data = jnp.arange(8.0).reshape(2, 4)   # each shard [1, 4]
        swapped = self._mapped(
            lambda x: all_to_all(x, 'data', split_dimension=1,
                                 concat_dimension=0), n=2)(data)
        # shard 0 keeps its first half and receives shard 1's first half
        np.testing.assert_array_equal(
            np.asarray(swapped), [[0, 1], [4, 5], [2, 3], [6, 7]])
