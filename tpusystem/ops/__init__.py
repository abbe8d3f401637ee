from tpusystem.ops.attention import attend, causal_mask, dot_product_attention
from tpusystem.ops.moe import (GatedExperts, MoEMLP, corrected_top_k,
                               expert_capacity, group_limited_top_k,
                               moe_partition_rules, route_top_k)
from tpusystem.ops.ssm import Mamba2, ssm_scan, ssm_update
from tpusystem.ops.ring import (ring_attention, ring_self_attention,
                                ulysses_attention, zigzag_ring_attention)

__all__ = ['attend', 'dot_product_attention', 'causal_mask', 'MoEMLP', 'GatedExperts',
           'group_limited_top_k', 'corrected_top_k', 'route_top_k',
           'Mamba2', 'ssm_scan', 'ssm_update',
           'expert_capacity', 'moe_partition_rules', 'ring_attention',
           'ring_self_attention', 'ulysses_attention', 'zigzag_ring_attention']
