"""The kernels of the chip_smoke.py train path, compiled for a described
TPU v5e at BERT-base shapes (bert_12_768_12, batch 32 x sequence 128).

The TPU's compiler is installed where the tests run; it compiles for a
chip that is described and not attached. Interpret mode cannot show what
it refuses (a block whose rows are no multiple of 8, too much VMEM), so
these compiles guard the main path at no chip time. A compile that
passes is not a chip run.

Only this file describes a topology, and only from inside the
module-scoped fixture: the process that does so loads the TPU library
and keeps it until it exits.
"""

import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the package re-exports functions under the modules' names
flash_mod = importlib.import_module('mxnet_tpu.ops.pallas.flash_attention')
norms_mod = importlib.import_module('mxnet_tpu.ops.pallas.fused_norms')
opt_mod = importlib.import_module('mxnet_tpu.ops.pallas.fused_optimizer')

BATCH, SEQ, UNITS, HEADS, HIDDEN, VOCAB = 32, 128, 768, 12, 3072, 30522

# every distinct parameter shape of the smoke's classifier that the fused
# optimizer's gate admits (test_shapes_cover_bert_base pins the list)
ADAM_SHAPES = [
    (UNITS,), (3 * UNITS,), (HIDDEN,),              # biases, LN gamma/beta
    (2, UNITS),                                     # token types, head
    (512, UNITS),                                   # positions
    (UNITS, UNITS), (3 * UNITS, UNITS),             # proj / pooler, qkv
    (HIDDEN, UNITS), (UNITS, HIDDEN),               # ffn1, ffn2
    (VOCAB, UNITS),                                 # word embedding
]


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:      # noqa: BLE001 - any failure means no compiler
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    # a compile for a described device is written to the persistent
    # cache but cannot be read back without a chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatch gates ask jax.devices(), which is the CPU here: steer
    them onto their Pallas branch, as the chip would."""
    for mod in (flash_mod, norms_mod):
        monkeypatch.setattr(mod, '_on_tpu', lambda: True)


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _assert_the_kernel_pair_alone(fn, seq, *shapes):
    """The compiled gradient of an attention call holds the forward and
    the backward kernel, no transpose round them and no (seq, seq)
    tensor."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert ' transpose(' not in text
    assert f'{seq},{seq}]' not in text


def test_shapes_cover_bert_base():
    """ADAM_SHAPES is what the model has: one encoder layer carries every
    distinct shape of twelve."""
    import mxnet_tpu as mx
    from chip_smoke import Config, build
    net, _, _, _ = build(Config(layers=1, batch=2, seq=8), mx.cpu(0))
    shapes = {tuple(p.shape) for p in net.collect_params().values()}
    admitted = {s for s in shapes if opt_mod._tileable(
        jax.ShapeDtypeStruct(s, jnp.float32))}
    assert admitted == set(ADAM_SHAPES)
    assert shapes - admitted == {(2,)}          # the head's bias: XLA


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_flash_attention_fwd_bwd_compiles(one_chip, on_tpu, dtype):
    """Head-major operands a whole tile wide (Llama's 128), every
    (batch, head) a group of its own: two custom calls, the forward
    kernel, which saves each row's logsumexp, and the backward kernel
    that rebuilds the tiles from it."""
    qkv = jax.ShapeDtypeStruct((8, 8, 512, 128), dtype, sharding=one_chip)

    def loss(q, k, v):
        out = flash_mod.flash_attention(q, k, v, causal=True)
        return (out.astype(jnp.float32) ** 2).sum()

    assert _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv) == 2


def test_flash_attention_under_a_tile_wide_takes_xla(one_chip, on_tpu):
    """Head-major operands 64 wide would move half-empty tiles (117 GB/s
    and slower than XLA on the chip, PERF.md §6 PR 34): the gate sends
    them to XLA, and no custom call is compiled."""
    qkv = jax.ShapeDtypeStruct((BATCH, HEADS, SEQ, UNITS // HEADS),
                               jnp.float32, sharding=one_chip)

    def loss(q, k, v):
        return (flash_mod.flash_attention(q, k, v) ** 2).sum()

    assert _compile(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv) == 0


def test_smoke_counts_a_kernel_site_for_every_layer(one_chip, on_tpu):
    """chip_smoke.py wants a forward and a backward kernel site a layer
    in one program of the step. The two kernel calls are jitted on their
    own, so the lowered program holds each once, in a private function
    that every layer calls: the smoke's count follows the calls."""
    from chip_smoke import kernel_sites
    from mxnet_tpu.ops.contrib import multi_head_attention
    x = jax.ShapeDtypeStruct((BATCH, SEQ, UNITS), jnp.float32,
                             sharding=one_chip)

    def loss(q, k, v):
        for _ in range(3):
            q = multi_head_attention(q, k, v, HEADS)
        return (q ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x)\
        .as_text()
    assert text.count('@tpu_custom_call') == 2
    sites = kernel_sites(text)
    assert sites['mx_flash_attention'] == 3
    assert sites['mx_flash_attention_bwd'] == 3


@pytest.mark.parametrize('batch, seq', [(BATCH, SEQ), (8, 512)],
                         ids=['b32_s128', 'b8_s512'])
def test_multi_head_attention_fwd_bwd_compiles(one_chip, on_tpu, batch,
                                               seq):
    """As BERT calls it, (batch, seq, 12 x 64) with no mask, at the smoke's
    shape and at bert_base.pretrain_b8_s512's own: the kernels take two
    heads a grid step, packed along the lanes as the projections leave
    them. Two custom calls, no transpose round them, and no (T, S) tensor
    in the program."""
    from mxnet_tpu.ops.contrib import multi_head_attention
    qkv = jax.ShapeDtypeStruct((batch, seq, UNITS), jnp.float32,
                               sharding=one_chip)

    def loss(q, k, v):
        return (multi_head_attention(q, k, v, HEADS) ** 2).sum()

    _assert_the_kernel_pair_alone(jax.grad(loss, argnums=(0, 1, 2)), seq,
                                  qkv, qkv, qkv)


@pytest.mark.parametrize('rows', [BATCH * SEQ, BATCH, 100])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16])
def test_layer_norm_fwd_bwd_compiles(one_chip, on_tpu, dtype, rows):
    # 4096 rows: the encoder; 32: a pooled vector; 100: no multiple of 8
    x = jax.ShapeDtypeStruct((rows, UNITS), dtype, sharding=one_chip)
    g = jax.ShapeDtypeStruct((UNITS,), jnp.float32, sharding=one_chip)

    def loss(x, gamma, beta):
        out = norms_mod.fused_layer_norm(x, gamma, beta, 1e-12)
        return (out.astype(jnp.float32) ** 2).sum()

    assert _compile(jax.grad(loss, argnums=(0, 1, 2)), x, g, g) == 1


def _opt_shapes(shape, one_chip):
    w = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    assert opt_mod._tileable(w), 'the gate sends this shape to XLA'
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return w, lr, t


def _adam(w, m, v, g, lr, wd, t):
    return opt_mod.adam_step(w, g, m, v, lr, wd, t, beta1=0.9,
                             beta2=0.999, epsilon=1e-8)


def _sgd_mom(w, mom, g, lr, wd):
    return opt_mod.sgd_mom_step(w, g, mom, lr, wd, momentum=0.9)


def _compiled_update(kind, shape, one_chip):
    """The update of one leaf as Trainer._fused_program compiles it: the
    weight and its slots donated, the gradient not."""
    w, lr, t = _opt_shapes(shape, one_chip)
    if kind == 'adam':
        return w, jax.jit(_adam, donate_argnums=(0, 1, 2)).lower(
            w, w, w, w, lr, lr, t).compile()
    return w, jax.jit(_sgd_mom, donate_argnums=(0, 1)).lower(
        w, w, w, lr, lr).compile()


# an instruction that moves data, with the shape it produces
_MOVES = re.compile(r'= f32\[([\d,]*)\]\S* (reshape|copy|transpose)\(')


def _leaf_moves(text, size):
    """The instructions of a compiled program that relayout an f32 array
    of ``size`` elements (the scalars' copies are not of a leaf's size)."""
    return [(op, dims) for dims, op in _MOVES.findall(text)
            if math.prod(int(d) for d in dims.split(',') if d) == size]


def _assert_the_kernel_alone(kind, shape, one_chip):
    """One custom call, every weight and slot written over its donated
    buffer, and no relayout of the leaf round the kernel: under the
    TPU's (8, 128) tiling a reshape of a leaf to (n, 128) rows and back
    is a read and a write of all of it (seven of them were 56 of the
    update's 84 bytes a parameter, PERF.md §6 PR 35). Bitcasts move
    nothing; the scalars' copies are not of the leaf's size."""
    w, compiled = _compiled_update(kind, shape, one_chip)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not _leaf_moves(text, w.size)
    slots = 3 if kind == 'adam' else 2
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        slots * 4 * w.size


@pytest.mark.parametrize('shape', ADAM_SHAPES, ids=str)
def test_adam_step_compiles(one_chip, shape):
    w, lr, t = _opt_shapes(shape, one_chip)
    assert _compile(_adam, w, w, w, w, lr, lr, t) == 1


@pytest.mark.parametrize('shape', [(UNITS,), (UNITS, HIDDEN),
                                   (VOCAB, UNITS)], ids=str)
def test_adam_step_writes_over_its_donated_operands(one_chip, shape):
    """What Trainer._fused_program donates: with w, m and v donated the
    chip's compiler aliases each output onto its operand, through the
    kernel's own input_output_aliases and the bitcasts round it."""
    from mxnet_tpu.analysis.rules.donation import \
        parse_input_output_aliases
    w, compiled = _compiled_update('adam', shape, one_chip)
    assert parse_input_output_aliases(compiled.as_text()) == \
        {0: 0, 1: 1, 2: 2}
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        3 * 4 * w.size


@pytest.mark.parametrize('shape', [(UNITS,), (2, UNITS), (UNITS, HIDDEN),
                                   (VOCAB, UNITS)], ids=str)
def test_sgd_mom_step_compiles(one_chip, shape):
    # the three shapes the old block rule had refused, and one it took
    w, lr, _ = _opt_shapes(shape, one_chip)
    assert _compile(_sgd_mom, w, w, w, lr, lr) == 1


@pytest.mark.parametrize('kind', ['adam', 'sgd_mom'])
@pytest.mark.parametrize('shape', ADAM_SHAPES, ids=str)
def test_update_holds_no_relayout_of_the_leaf(one_chip, kind, shape):
    _assert_the_kernel_alone(kind, shape, one_chip)


def _assert_xlas_fusion_in_place(shape, one_chip):
    """The registered fused Adam step at ``shape``: no custom call, no
    relayout of the leaf round its fusion, the weight and both slots
    written over their donated buffers."""
    from mxnet_tpu.ops.optimizer_ops import fused_adam_step
    w = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    t = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def step(w, m, v, g, lr, wd, t):
        return fused_adam_step(w, g, m, v, lr=lr, wd=wd, t=t)

    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        w, w, w, w, lr, lr, t).compile()
    text = compiled.as_text()
    assert 'tpu_custom_call' not in text
    assert not _leaf_moves(text, w.size)
    assert compiled.memory_analysis().alias_size_in_bytes >= 3 * 4 * w.size


@pytest.mark.parametrize('shape', [(VOCAB, UNITS), (VOCAB,), (UNITS,),
                                   (16, 768, 2048), (16032, 2048)], ids=str)
def test_the_registered_update_is_xlas_fusion_in_the_leafs_layout(
        one_chip, shape):
    """What ships since PR 35 (the gate is closed: XLA's fusion measured
    as fast as the kernel on the chip, PERF.md §6): the registered op
    compiles to no custom call, moves no leaf round its fusion and
    writes the weight and both slots over their donated buffers, at a
    kernel's shape and at one no kernel takes (the decoder's bias)."""
    _assert_xlas_fusion_in_place(shape, one_chip)


# ------------------------------------------------------------------------
# The kernels of the deepseek_v3 train path at kanana_2_30b_a3b's widths
# (chipbench/configs/kanana_2_30b_a3b.json: 2 rows x 1024 positions, 32
# heads of 192 / 128, experts of 768, 16 of 128 held, 6 a token).

MLA_ROWS, MLA_SEQ, MLA_UNITS, MLA_HEADS = 2, 1024, 2048, 32
MLA_ADAM_SHAPES = [
    (16, 768, 2048), (16, 2048, 768),       # experts stacked in one leaf
    (16032, 2048),                          # embedding, head (a slice)
    (6144, 2048), (2048, 4096),             # q_proj / dense FFN, o_proj
    (576, 2048), (8192, 512),               # latent down and up
    (128, 2048), (1536, 2048),              # router, shared expert
    (2048,), (512,),                        # RMSNorm gains
]
# the two down-projections (dense FFN, shared expert): with the list above
# every distinct trainable shape of DeepseekV3ForCausalLM at these widths
MLA_DOWN_SHAPES = [(2048, 6144), (2048, 1536)]


def test_latent_attention_fwd_bwd_compiles(one_chip, on_tpu):
    """kanana_2_30b_a3b.pretrain_b2_s1024's own shape: q and k 192 wide,
    v 128, causal, float32. The kernels take two heads a grid step (384
    and 256 lanes) and v at its own width: two custom calls, forward and
    backward, no transpose round them and no (1024, 1024) tensor."""
    from mxnet_tpu.ops.contrib import multi_head_attention
    qk = jax.ShapeDtypeStruct((MLA_ROWS, MLA_SEQ, MLA_HEADS * 192),
                              jnp.float32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((MLA_ROWS, MLA_SEQ, MLA_HEADS * 128),
                             jnp.float32, sharding=one_chip)

    def loss(q, k, v):
        out = multi_head_attention(q, k, v, MLA_HEADS, causal=True,
                                   sm_scale=192 ** -0.5)
        assert out.shape == (MLA_ROWS, MLA_SEQ, MLA_HEADS * 128)
        return (out ** 2).sum()

    _assert_the_kernel_pair_alone(jax.grad(loss, argnums=(0, 1, 2)),
                                  MLA_SEQ, qk, qk, v)


@pytest.mark.parametrize('units', [MLA_UNITS, 512])
def test_rms_norm_fwd_bwd_compiles(one_chip, on_tpu, units):
    x = jax.ShapeDtypeStruct((MLA_ROWS * MLA_SEQ, units), jnp.float32,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((units,), jnp.float32, sharding=one_chip)

    def loss(x, gamma):
        return (norms_mod.fused_rms_norm(x, gamma, 1e-6) ** 2).sum()

    assert _compile(jax.grad(loss, argnums=(0, 1)), x, g) == 1


def _assert_every_rung_takes_the_grouped_kernels(text, held, a_rung):
    """A cell's 2,048 tokens x 6 on ``held`` of 128 experts: each prefix
    of its ladder (``ops/experts.py`` ``prefix_ladder``) has ``a_rung``
    grouped-matmul kernels over its own rows, forward (once more for the
    backward's own recomputation) and the two gradients of each, and no
    dense product over every expert; and its operations carry the scope
    ``rows_<P>`` inside ``mx.experts``, forward and backward, which is
    how a profile says which rung ran."""
    from mxnet_tpu.ops.experts import SCOPE, prefix_ladder
    ladder = prefix_ladder(MLA_ROWS * MLA_SEQ * 6, held, 128)
    assert 1 < len(ladder) <= 5 and ladder[-1] == MLA_ROWS * MLA_SEQ * 6
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    for p in ladder:
        assert sum(f'f32[{p},' in line for line in kernels) == a_rung, p
        assert f'f32[{held},{p},' not in text
        names = re.findall(rf'op_name="([^"]*rows_{p}\b[^"]*)"', text)
        assert names and all(SCOPE in n[:n.index('rows_')] for n in names)
        assert {n.startswith('jit(loss)/transpose(') for n in names} == \
            {False, True}


def test_sparse_experts_take_the_grouped_kernels(one_chip):
    """jax.lax.ragged_dot at the cell's shapes lowers to the compiler's
    own grouped-matmul kernels, forward and backward, on every rung of
    the cell's ladder, and not to the dense product over every expert
    (an f32[16, rows, ...] buffer)."""
    from mxnet_tpu.ops.experts import sparse_experts
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    shapes = (s(MLA_ROWS, MLA_SEQ, MLA_UNITS), s(128, MLA_UNITS), s(128),
              s(16, 768, MLA_UNITS), s(16, 768, MLA_UNITS),
              s(16, MLA_UNITS, 768))

    def loss(*a):
        return (sparse_experts(*a, experts_per_token=6, first_expert=0,
                               routed_scaling_factor=2.448) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        *shapes).compile().as_text()
    # three products forward, gate and up again, six gradients
    _assert_every_rung_takes_the_grouped_kernels(text, 16, 11)


@pytest.mark.parametrize('shape', MLA_ADAM_SHAPES, ids=str)
def test_adam_step_compiles_at_the_sparse_decoders_shapes(one_chip, shape):
    w, compiled = _compiled_update('adam', shape, one_chip)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        3 * 4 * w.size


@pytest.mark.parametrize('kind', ['adam', 'sgd_mom'])
@pytest.mark.parametrize('shape', MLA_ADAM_SHAPES + MLA_DOWN_SHAPES,
                         ids=str)
def test_update_holds_no_relayout_at_the_sparse_decoders_shapes(
        one_chip, kind, shape):
    """The stacked experts' leaves collapse to (12288, 2048) and
    (32768, 768) rows by a bitcast: 768 and 2048 fill whole sublane
    tiles."""
    _assert_the_kernel_alone(kind, shape, one_chip)


@pytest.mark.parametrize('shape', [(8, 2 ** 19), (1, 24, 2 ** 17)],
                         ids=str)
def test_update_splits_a_last_axis_too_long_for_a_block(one_chip, shape):
    """Eight rows of 2**19 floats are 16 MiB an operand: the grid's
    second axis takes the last axis in whole 128-lane tiles, the kernel
    still sees the leaf as it lies, and the declared VMEM holds."""
    rows, cols = opt_mod._rows_view(shape)
    bn, lanes = opt_mod._block_rows(rows, cols, 7)
    assert lanes < cols and cols % lanes == 0 and lanes % 128 == 0
    _assert_the_kernel_alone('adam', shape, one_chip)


# ------------------------------------------------------------------------
# The ops of the nemotron_h train path at nemotron_twotower_30b_a3b's
# widths (chipbench/configs/nemotron_twotower_30b_a3b.json: 2 rows x 1024
# positions, 2688 wide; 64 Mamba heads of 64, 8 groups, state 128, conv 4,
# chunk 128; 32 query and 2 key/value heads of 128; experts of 1856, 8 of
# 128 held, 6 a token).

NH_ROWS, NH_SEQ, NH_UNITS = 2, 1024, 2688
NH_HEADS, NH_P, NH_GROUPS, NH_STATE, NH_CHUNK = 64, 64, 8, 128, 128
NH_CONV = NH_HEADS * NH_P + 2 * NH_GROUPS * NH_STATE          # 6144
# every distinct trainable shape of NemotronHForCausalLM at these widths
NH_ADAM_SHAPES = [
    (64,), (6144, 4), (6144,), (4096,),         # A_log / D / dt_bias, conv
    (10304, 2688), (2688, 4096),                # in_proj, out_proj / o_proj
    (8, 1856, 2688), (8, 2688, 1856),           # experts stacked in a leaf
    (16384, 2688),                              # embedding, head (a slice)
    (2688,), (128, 2688),                       # RMSNorm gains, router
    (3712, 2688), (2688, 3712),                 # the shared expert
    (4096, 2688), (256, 2688),                  # q_proj; k_proj, v_proj
]


def test_the_nemotron_shapes_are_what_the_model_has():
    """NH_ADAM_SHAPES against the zoo's own leaves: a period at the
    published widths, no array made."""
    import json
    import os
    from mxnet_tpu.gluon.model_zoo.nemotron_h import (NemotronHConfig,
                                                      NemotronHForCausalLM)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from chipbench.flops.nemotron_h import moved_param_count
    with open(os.path.join(here, 'chipbench', 'configs',
                           'nemotron_twotower_30b_a3b.json')) as f:
        cfg = json.load(f)
    params = NemotronHForCausalLM(NemotronHConfig(**cfg)).collect_params()
    assert {tuple(p.shape) for p in params.values()
            if p.grad_req != 'null'} == set(NH_ADAM_SHAPES)
    # 528.1 M under Adam, by the leaves and by the benchmark's count
    assert sum(math.prod(p.shape) for p in params.values()
               if p.grad_req != 'null') == moved_param_count(cfg) \
        == 528_092_736


def _scan_shapes(one_chip):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    return (s(NH_ROWS, NH_SEQ, NH_HEADS, NH_P), s(NH_ROWS, NH_SEQ, NH_HEADS),
            s(NH_HEADS), s(NH_ROWS, NH_SEQ, NH_GROUPS, NH_STATE),
            s(NH_ROWS, NH_SEQ, NH_GROUPS, NH_STATE), s(NH_HEADS))


def test_ssm_scan_fwd_bwd_compiles(one_chip):
    """The chunked scan at the cell's shapes, forward and backward: plain
    XLA (no custom call), and what it keeps for the backward is its
    inputs, not a (128, 128) array a head: the program's peak stays under
    eight of them (67 MB each)."""
    from mxnet_tpu.ops.ssm import ssm_scan

    def loss(*a):
        return (ssm_scan(*a, chunk_size=NH_CHUNK) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *_scan_shapes(one_chip)).compile()
    assert 'tpu_custom_call' not in compiled.as_text()
    per_head_square = 4 * NH_ROWS * NH_SEQ * NH_HEADS * NH_CHUNK
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 8 * per_head_square


def test_ssm_conv_fwd_bwd_compiles(one_chip):
    from mxnet_tpu.ops.ssm import ssm_conv
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)

    def loss(x, w, b):
        return (ssm_conv(x, w, b) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        s(NH_ROWS, NH_SEQ, NH_CONV), s(NH_CONV, 4), s(NH_CONV)) \
        .compile().as_text()
    assert 'tpu_custom_call' not in text


def test_grouped_query_attention_takes_the_flash_pair(one_chip, on_tpu):
    """32 query heads of 128 over 1024 positions, K and V repeated from 2
    heads as the zoo's layer does: the kernels take one head a grid step.
    Two custom calls, forward and backward, and no (1024, 1024) tensor."""
    from mxnet_tpu.ops.contrib import multi_head_attention
    s = lambda heads: jax.ShapeDtypeStruct(
        (NH_ROWS, NH_SEQ, heads * 128), jnp.float32, sharding=one_chip)

    def loss(q, k, v):
        rep = lambda a: jnp.repeat(
            a.reshape(NH_ROWS, NH_SEQ, 2, 128), 16, axis=2).reshape(
            NH_ROWS, NH_SEQ, -1)
        out = multi_head_attention(q, rep(k), rep(v), 32, causal=True)
        return (out ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        s(32), s(2), s(2)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert f'{NH_SEQ},{NH_SEQ}]' not in text


def test_un_gated_experts_take_the_grouped_kernels(one_chip):
    """relu2 experts at the cell's shapes (8 held of 128, 1856 wide): the
    two grouped products and their gradients lower to the compiler's own
    grouped-matmul kernels on every rung of the cell's ladder, as the
    three of a SwiGLU do above."""
    from mxnet_tpu.ops.experts import sparse_experts
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    shapes = (s(NH_ROWS, NH_SEQ, NH_UNITS), s(128, NH_UNITS), s(128),
              s(8, 1856, NH_UNITS), s(8, NH_UNITS, 1856))

    def loss(x, rw, rb, up, down):
        return (sparse_experts(x, rw, rb, None, up, down,
                               experts_per_token=6, first_expert=0,
                               routed_scaling_factor=2.5,
                               activation='relu2') ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4))).lower(
        *shapes).compile().as_text()
    # two products forward, up again, four gradients
    _assert_every_rung_takes_the_grouped_kernels(text, 8, 7)


@pytest.mark.parametrize('shape', NH_ADAM_SHAPES, ids=str)
def test_the_update_at_the_hybrids_shapes_is_one_fusion_in_place(
        one_chip, shape):
    """The registered fused Adam step (XLA's fusion since PR 35) at every
    leaf shape of the hybrid, the 1-D (64,) and the (6144, 4) convolution
    among them: no custom call, no relayout of the leaf, the weight and
    both slots written over their donated buffers."""
    _assert_xlas_fusion_in_place(shape, one_chip)


# ------------------------------------------------------------------------
# The delta rule of the kimi_linear train path at kimi_linear_48b_a3b's
# widths (chipbench/configs/kimi_linear_48b_a3b.json: 2 rows x 1024
# positions, 2304 wide; KDA of 32 heads of 128, chunks of 64).

KL_ROWS, KL_SEQ, KL_HEADS, KL_D, KL_CHUNK = 2, 1024, 32, 128, 64


def test_the_kimi_shapes_are_what_the_model_has():
    """602.4 M parameters under Adam, by the zoo's leaves and by the
    benchmark's count, no array made."""
    import json
    import os
    from mxnet_tpu.gluon.model_zoo.kimi_linear import (
        KimiLinearConfig, KimiLinearForCausalLM)
    from chipbench.flops.kimi_linear import moved_param_count
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, 'chipbench', 'configs',
                           'kimi_linear_48b_a3b.json')) as f:
        cfg = json.load(f)
    net = KimiLinearForCausalLM(KimiLinearConfig(**cfg))
    mixer = net.model.layers[0].self_attn
    assert mixer.q_proj.weight.shape == (KL_HEADS * KL_D, 2304)
    assert mixer._chunk == KL_CHUNK
    params = net.collect_params()
    # LlamaMLP's Dense layers learn their input width at the first call:
    # 2304 for gate and up, the width of gate's output for down
    width = lambda n, p: 2304 if 'down_proj' not in n else params[
        n.replace('down_proj', 'gate_proj')].shape[0]
    assert sum(math.prod(p.shape[:-1]) * (p.shape[-1] or width(n, p))
               for n, p in params.items() if p.grad_req != 'null') \
        == moved_param_count(cfg) == 602_433_408


def test_kda_scan_fwd_bwd_compiles(one_chip):
    """The chunked delta rule at the cell's shapes, forward and backward:
    plain XLA (no Pallas kernel; the inverses are XLA's products), and
    it never holds a whole chunk's (position, position, channel) decays:
    its temporaries stay under half of one layer's of them (2.1 GB)."""
    from mxnet_tpu.ops.kda import kda_scan
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    qk = s(KL_ROWS, KL_SEQ, KL_HEADS, KL_D)

    def loss(*a):
        return (kda_scan(*a, chunk_size=KL_CHUNK) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(5)))).lower(
        qk, qk, qk, qk, s(KL_ROWS, KL_SEQ, KL_HEADS)).compile()
    assert 'tpu_custom_call' not in compiled.as_text()
    whole_chunk = 4 * KL_ROWS * KL_SEQ * KL_HEADS * KL_CHUNK * KL_D
    assert compiled.memory_analysis().temp_size_in_bytes < whole_chunk / 2


# ------------------------------------------------------------------------
# The lfm2_moe train path at lfm2_24b_a2b's widths
# (chipbench/configs/lfm2_24b_a2b.json: 2 rows x 2048 positions, 2048
# wide; short convolutions of 3 taps; 32 query and 8 key/value heads of
# 64).

LF_ROWS, LF_SEQ, LF_UNITS, LF_HEADS, LF_KV = 2, 2048, 2048, 32, 8


def test_the_lfm2_shapes_are_what_the_model_has():
    """469.3 M parameters under Adam, by the zoo's leaves and by the
    benchmark's count, no array made; the head is the embedding."""
    import json
    import os
    from mxnet_tpu.gluon.model_zoo.lfm2_moe import (Lfm2MoeConfig,
                                                    Lfm2MoeForCausalLM)
    from chipbench.flops.lfm2_moe import moved_param_count
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, 'chipbench', 'configs',
                           'lfm2_24b_a2b.json')) as f:
        cfg = json.load(f)
    params = Lfm2MoeForCausalLM(Lfm2MoeConfig(**cfg)).collect_params()
    assert params['model.layers0.conv.in_proj.weight'].shape == \
        (3 * LF_UNITS, LF_UNITS)
    assert params['model.layers1.self_attn.k_proj.weight'].shape == \
        (LF_KV * 64, LF_UNITS)
    assert not any('lm_head' in n for n in params)
    assert sum(math.prod(p.shape) for p in params.values()
               if p.grad_req != 'null') == moved_param_count(cfg) \
        == 469_284_992


def test_short_conv_fwd_bwd_compiles(one_chip):
    """The gates and the taps at the cell's shapes, forward and backward:
    plain XLA, no custom call."""
    from mxnet_tpu.ops.ssm import short_conv
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                            sharding=one_chip)
    x = s(LF_ROWS, LF_SEQ, LF_UNITS)

    def loss(b, c, x, w):
        return (short_conv(b, c, x, w) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        x, x, x, s(LF_UNITS, 3)).compile().as_text()
    assert 'tpu_custom_call' not in text


def test_lfm2_attention_takes_the_flash_pair(one_chip, on_tpu):
    """32 query heads of 64 over 2048 positions, K and V repeated from 8
    heads as the zoo's layer does: two custom calls, forward and
    backward, and no (heads, 2048, 2048) tensor."""
    from mxnet_tpu.ops.contrib import multi_head_attention
    s = lambda heads: jax.ShapeDtypeStruct(
        (LF_ROWS, LF_SEQ, heads * 64), jnp.float32, sharding=one_chip)

    def loss(q, k, v):
        rep = lambda a: jnp.repeat(
            a.reshape(LF_ROWS, LF_SEQ, LF_KV, 64), LF_HEADS // LF_KV,
            axis=2).reshape(LF_ROWS, LF_SEQ, -1)
        out = multi_head_attention(q, rep(k), rep(v), LF_HEADS, causal=True)
        return (out ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        s(LF_HEADS), s(LF_KV), s(LF_KV)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert f'{LF_HEADS},{LF_SEQ},{LF_SEQ}]' not in text
