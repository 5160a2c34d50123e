"""Memory-fit planning (distributed/scale_plan.py): the 10B/v5p-64 and
1.3B/v5e mandates, scaling laws of the estimator, and the hybrid ZeRO-3
spec merger used by dryrun phase 7."""
import pytest

from paddle_tpu.distributed import scale_plan as sp


def test_param_count_matches_init_params():
    """The closed-form block/embed param counts must agree with the real
    init_params pytree (else every downstream byte number is fiction)."""
    import jax
    from paddle_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=3,
                        num_heads=4, max_seq_len=32, dtype='float32',
                        remat=False, use_flash=False)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    real = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    dims = sp.ModelDims(vocab_size=128, hidden_size=64, num_layers=3,
                        num_heads=4, max_seq_len=32)
    assert dims.n_params == real


def test_param_count_matches_init_params_gqa():
    import jax
    from paddle_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=96, hidden_size=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, max_seq_len=16,
                        dtype='float32', remat=False, use_flash=False)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    real = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    dims = sp.ModelDims(vocab_size=96, hidden_size=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, max_seq_len=16)
    assert dims.n_params == real


def test_1p3b_fits_v5e_with_bf16_everything():
    """The 1.3B widths' memory story: bf16 params + bf16 moments +
    full remat fit one 16 GiB v5e chip..."""
    plan = sp.assert_fits(sp.gpt_1p3b_dims(), sp.gpt_1p3b_v5e_layout(),
                          sp.HBM_GB['v5e'], label='gpt1.3b/v5e')
    assert 1.2e9 < plan['n_params'] < 1.4e9
    assert plan['total_gib'] < 16 * 0.9


def test_1p3b_f32_master_does_not_fit_v5e():
    """...while the f32-params variant exceeds it — the reason the rung
    pins bf16 numerics."""
    layout = sp.gpt_1p3b_v5e_layout()
    layout.param_dtype = 'float32'
    layout.moment_dtype = 'float32'
    with pytest.raises(MemoryError):
        sp.assert_fits(sp.gpt_1p3b_dims(), layout, sp.HBM_GB['v5e'])


def test_ernie10b_fits_v5p64():
    """The north-star fit proof: ~10B params, dp4 x mp4 x pp4, ZeRO-1."""
    dims, layout = sp.ernie10b_dims(), sp.ernie10b_v5p64_layout()
    assert layout.n_devices == 64
    plan = sp.assert_fits(dims, layout, sp.HBM_GB['v5p'],
                          label='ernie10b/v5p-64')
    assert 9e9 < plan['n_params'] < 11e9


def test_ernie10b_single_chip_does_not_fit():
    """10B with replicated f32 Adam needs ~150 GiB — no single chip holds
    it; the hybrid layout is what makes the mandate possible."""
    with pytest.raises(MemoryError):
        sp.assert_fits(sp.ernie10b_dims(), sp.Layout(micro_batch=1),
                       sp.HBM_GB['v5p'])


def test_zero_stages_shrink_memory_monotonically():
    dims = sp.ernie10b_dims()
    totals = []
    for z in (0, 1, 2, 3):
        layout = sp.Layout(dp=8, micro_batch=1, zero_stage=z)
        totals.append(sp.plan_memory(dims, layout)['total_gib'])
    assert totals == sorted(totals, reverse=True)
    assert totals[3] < totals[0] / 3          # zero3 shards p+g+os over dp8


def test_parallel_degrees_shrink_components():
    dims = sp.ernie10b_dims()
    base = sp.plan_memory(dims, sp.Layout(micro_batch=1))
    mp4 = sp.plan_memory(dims, sp.Layout(mp=4, micro_batch=1))
    pp4 = sp.plan_memory(dims, sp.Layout(pp=4, micro_batch=1))
    sp2 = sp.plan_memory(dims, sp.Layout(sp=2, micro_batch=1))
    assert mp4['params_gib'] < base['params_gib'] / 3
    assert pp4['params_gib'] < base['params_gib'] / 3
    assert pp4['activations_gib'] < base['activations_gib']
    assert sp2['activations_gib'] < base['activations_gib']
    assert sp2['loss_head_gib'] == pytest.approx(
        base['loss_head_gib'] / 2)


def test_blockwise_xent_head_memory():
    """At vocab 128k the naive head is ~GBs of f32 logits; blockwise is
    bounded by the chunk (the bench vocab128k A/B's memory story)."""
    dims = sp.ModelDims(vocab_size=131072, hidden_size=1024, num_layers=24,
                        num_heads=16, max_seq_len=1024)
    naive = sp.plan_memory(dims, sp.Layout(micro_batch=8, xent_chunk=0))
    blockwise = sp.plan_memory(dims, sp.Layout(micro_batch=8,
                                               xent_chunk=8192))
    assert naive['loss_head_gib'] >= 4.0   # [8,1024,131072] f32 = 4 GiB
    assert blockwise['loss_head_gib'] < 0.3


def test_hybrid_zero3_specs_merge():
    """dp sharding lands only on dims mp/pp left unsharded, and only when
    divisible."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.models import gpt
    from paddle_tpu.parallel.zero import hybrid_zero3_specs

    devs = np.array(jax.devices('cpu')[:8]).reshape(2, 2, 2)
    mesh = Mesh(devs, ('dp', 'mp', 'pp'))
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                        num_heads=2, max_seq_len=16, dtype='float32',
                        remat=False, use_flash=False, mp=2, pp=2)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    specs = hybrid_zero3_specs(params, gpt.param_specs(cfg), mesh)
    # qkv_w [L, h, 3h]: pp on L, mp on cols -> dp must land on h (dim 1)
    assert specs['blocks']['qkv_w'] == P('pp', 'dp', 'mp')
    # wte [V, H]: mp on rows -> dp on H
    assert specs['wte'] == P('mp', 'dp')
    # tiny 1-D ln scale [h]: h=32 divisible by dp=2 -> dp lands there
    assert specs['lnf_g'] == P('dp')
