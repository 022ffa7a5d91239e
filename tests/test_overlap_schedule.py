"""Comm/compute overlap: static schedule proof (tools/overlap/aot_overlap.py).

Compiles the framework's distributed paths for a described v5e:2x4
topology (the TPU's compiler, no chips needed), in the test's own
process, and asserts what the scheduled HLO shows:

* ring attention overlaps the K/V ICI transfer with the flash-attention
  block compute (collective-permute-start ... compute ... -done);
* a DP training step through the framework's own code (pure_function
  forward, kvstore.fusion.bucketed_allreduce_in_axis — the store's
  shared bucket planner — and the registry's sgd_mom_update) coalesces
  per-key gradients into bucket collectives (2(N-1)/N wire bytes) and
  schedules compute between all-reduce start and done.

Reference parity anchor: src/kvstore/p3store_dist.h (priority
slice-and-schedule existed to get exactly this overlap/fusion behavior).
"""
import pytest


@pytest.fixture(scope='module')
def topo():
    """Describe the topology once a test of this file runs, never while
    the module is imported: the process that does it loads the TPU
    library and keeps it until it exits."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x4')
    except Exception as e:      # noqa: BLE001 - any failure means no compiler
        pytest.skip(f'no v5e:2x4 topology can be described here: {e}')


@pytest.fixture(scope='module')
def analyses(topo):
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools', 'overlap'))
    import aot_overlap
    return (aot_overlap.analyze_ring_attention(),
            aot_overlap.analyze_dp_step())


@pytest.mark.serial
def test_ring_attention_permute_overlaps_compute(analyses):
    ring, _ = analyses
    assert ring['async_permute_starts'] >= 2          # K and V blocks
    assert ring['async_permute_dones'] == ring['async_permute_starts']
    assert ring['attention_block_inside_window'], \
        'flash-attention block not scheduled inside the permute window'
    assert ring['verdict'].startswith('OVERLAPPED')
    # the ring must be a one-hop neighbor exchange (ICI-friendly)
    assert '{0,1}' in ring['ring_source_target_pairs']
    assert '{7,0}' in ring['ring_source_target_pairs']


@pytest.mark.serial
def test_dp_trainer_path_buckets_fuse_and_overlap(analyses):
    _, dp = analyses
    # the analyzed program is the framework's code, not a synthetic MLP
    assert 'bucketed_allreduce_in_axis' in dp['framework_path']
    assert 'pure_function' in dp['framework_path']
    assert 'sgd_mom_update' in dp['framework_path']
    # fusion buffers: 14 param keys (7 layers x W,b) -> few collectives
    assert dp['param_keys'] >= 14
    rep = dp['replicated_update']
    assert 0 < rep['collectives_in_schedule'] < dp['param_keys']
    assert rep['verdict'].startswith('FUSED')
    # ZeRO-1 (the default Trainer path at nproc>1): sharded optimizer
    # compute scheduled BETWEEN the grad scatter and the weight gather
    z1 = dp['zero1_update']
    assert z1['grad_scatter_collectives'] >= 1
    assert z1['all_gathers'] >= 1
    assert z1['optimizer_compute_between_collectives'] >= 1
    assert z1['verdict'].startswith('SHARDED+INTERLEAVED')
