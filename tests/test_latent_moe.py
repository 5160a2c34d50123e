"""The latent-attention, routed-expert family (models/latent_moe.py) against
its plain reference (benchmark/reference/dots_vlm.py), at small sizes on the
CPU with seeded weights: the absorbed form against the expanded, the
group-limited choice against a brute-force numpy choice, the shares of an
expert-parallel layer adding up to the uncut one, no row dropped under a
skewed router, each new kernel interpreted against ``jax.numpy``, and the
``moe.*`` counters. The engine's contract (the served logits among it) is
tests/family_contract.py's, bound to this family's row of
tests/served_families.py; what stays here is the family's own."""
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import family, latent_moe
from paddle_tpu.ops import expert_grouped_matmul as gmm
from paddle_tpu.ops import paged_kv
from paddle_tpu.ops import paged_latent_attention as pla
from paddle_tpu.parallel import routed_experts as rexp
from paddle_tpu.serving import GenerationEngine

from family_contract import Contract, borrow, served_of
from served_families import FAMILIES, REPO

fa = importlib.import_module('paddle_tpu.ops.flash_attention')
row = FAMILIES['latent_moe']
served = served_of(row)
ref = row.ref
SHAPE = row.shape()
config_of = row.config


class TestLatentMoeContract(Contract):
    row = FAMILIES['latent_moe']


@pytest.fixture
def interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


@pytest.fixture(scope='module')
def weights():
    """The reference's float32 weights, which the program takes as they
    are."""
    return served.stacked


def test_the_kernels_admit_a_request_while_another_decodes():
    """The contract's run through the interpreted kernels (its rows are
    held to the reference there) is three requests on two slots over pages
    of 128: the third takes the slot the second leaves after 5 tokens and
    is prefilled, and decodes its 6 through the latent kernel, while the
    first is still at its 12. The first's 11 steps hold every other step:
    an engine that admitted the third after the first would run 11 + 5."""
    run, _, new, _ = served.kernel
    assert [len(p) for p in run.prompts] == [20, 9, 13]
    assert [len(t) for t in run.tokens] == list(new) == [12, 5, 6]
    assert run.stats['completed'] == 3 == run.stats['prefills']
    assert run.stats['steps'] == new[0] - 1 < (new[0] - 1) + (new[2] - 1)
    assert run.stats['traces'] == 2         # all three prompts in 32 rows


@pytest.mark.parametrize('temperature', [0.0, 0.8],
                         ids=['greedy', 'sampled'])
def test_one_step_ahead_serves_what_reading_first_serves(
        weights, temperature, read_first):
    """The decode loop dispatches step N+1 before it reads step N (PR 36),
    and the routed layers' counts ride behind the tokens one dispatch
    later: the same tokens, the same logits to the last bit and the same
    counts as a loop that reads each step before it dispatches the next.
    A request a slot, queued before the engine starts, so that every step
    holds the same rows in both orders (a routed layer groups them)."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 64, size=n).astype(np.int32) for n in (20, 9)]
    held = lambda: getattr(obs.find(                        # noqa: E731
        'moe.rows_held_total', {'phase': 'decode'}), 'value', 0)

    engines = []

    def serve():
        eng = GenerationEngine(weights, config_of(SHAPE), num_slots=2,
                               page_size=16, num_pages=9, prefill_width=32,
                               temperature=temperature, autostart=False)
        if engines:     # reading first runs the same executables
            borrow(eng, engines[0])
        engines.append(eng)
        futs = [eng.submit(p, max_new_tokens=6 + 5 * i, seed=i,
                           want_logits=True) for i, p in enumerate(prompts)]
        before = held()
        with eng:
            out = [(f.result(timeout=300), np.stack(f.logits()))
                   for f in futs]
            return out, eng.stats(), held() - before

    got, stats, counted = serve()
    with read_first():
        want, base, counted_first = serve()
    assert base['steps_overlapped'] == 0 < stats['steps_overlapped']
    # the step and the prefill at 32 and at 16 rows
    assert stats['steps'] == base['steps'] and stats['traces'] == 3
    # reading first traced nothing: not of its own and, borrowing, not
    # where its calls would land a trace, on the first engine
    assert base['traces'] == 0 and engines[0]._trace_count == 3
    assert counted == counted_first > 0
    for (toks, rows), (want_toks, want_rows) in zip(got, want):
        assert toks == want_toks
        np.testing.assert_array_equal(rows, want_rows)


def test_absorbed_form_equals_expanded_form(weights):
    """A decode step over the pool (absorbed) gives the row the prefill
    (expanded) gives for the same token at the same place."""
    cfg, params = config_of(SHAPE), weights
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 64)
    full = latent_moe.forward(params, toks, cfg)
    pool = latent_moe.init_pool(cfg, 5, 16)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    cache = dict(pool, page_table=table, valid=jnp.asarray([23, 23]))
    _, cache = latent_moe.forward_with_cache(
        params, toks[:, :23], cache, jnp.zeros((2,), jnp.int32), cfg)
    row, _ = latent_moe.forward_with_cache(
        params, toks[:, 23:], dict(latent=cache['latent'], page_table=table),
        jnp.asarray([23, 23], jnp.int32), cfg)
    np.testing.assert_allclose(row[:, 0], full[:, 23], atol=2e-5)


@pytest.fixture(scope='module')
def three_bodies(weights):
    """ONE engine of bodies of 128, 256 and 320 rows for the cases below:
    a body is traced and compiled by the first case that runs it."""
    shape = dict(SHAPE, max_position_embeddings=384)
    with GenerationEngine(weights, config_of(shape), num_slots=1,
                          page_size=128, num_pages=4,
                          prefill_width=320) as eng:
        yield eng


@pytest.mark.parametrize('rows,body', [(20, 128), (256, 256), (257, 320),
                                       (300, 320)])
def test_a_padded_prefill_runs_the_narrowest_body_that_holds_it(
        weights, three_bodies, rows, body):
    """The engine pads a prompt to the narrowest of its widths that holds
    it (128, 256 and the full 320 rows here) and runs the prefill at that
    shape; the family's forward gives the reference's row and writes the
    same cache rows at either width, the padding routed nowhere."""
    shape = dict(SHAPE, max_position_embeddings=384)
    cfg, params = config_of(shape), weights
    prompt = np.random.RandomState(rows).randint(0, 64, size=rows).astype(
        np.int32)
    eng = three_bodies
    assert eng.prefill_widths == (128, 256, 320)
    before = eng.stats()
    fut = eng.submit(prompt, max_new_tokens=1, want_logits=True)
    fut.result(timeout=300)
    stats = eng.stats()
    for key, want in (('prefill_rows_asked', rows),
                      ('prefill_rows_computed', body)):
        assert stats[key] - before[key] == want
    want = ref.forward(weights, jnp.asarray(prompt)[None], shape)
    np.testing.assert_allclose(fut.logits()[0], want[0, -1], atol=2e-5)

    def prefill(width):
        toks = np.zeros((1, width), np.int32)
        toks[0, :rows] = prompt
        cache = dict(latent_moe.init_pool(cfg, 4, 128),
                     page_table=jnp.asarray([[1, 2, 3]], jnp.int32),
                     valid=jnp.asarray([rows], jnp.int32))
        logits, out = latent_moe.forward_with_cache(
            params, jnp.asarray(toks), cache, jnp.zeros((1,), jnp.int32),
            cfg, last_only=True)
        written = np.asarray(out['latent'][:, 1:4]).reshape(3, -1, 256)
        return np.asarray(logits[0, 0]), written[:, :rows], out['counts']
    (row, written, counts), (full_row, full, _) = prefill(body), prefill(320)
    np.testing.assert_allclose(row, want[0, -1], atol=2e-5)
    np.testing.assert_allclose(row, full_row, atol=2e-5)
    np.testing.assert_allclose(written, full, atol=2e-5)
    assert int(counts[0]) == 2 * 4 * rows       # padding routed nowhere


def test_the_programs_weights_have_the_references_structure():
    cfg = config_of(SHAPE, param_dtype='bfloat16')
    mine = latent_moe.init_params(cfg, jax.random.PRNGKey(0))
    theirs = ref.init_params(SHAPE, jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(theirs))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_yarn_frequencies_follow_the_published_blend():
    cfg = latent_moe.LatentMoEConfig(num_hidden_layers=1, held=(0, 16))
    got = np.asarray(latent_moe.rotary_inv_freq(cfg))
    f = 10000.0 ** (-2.0 * np.arange(32) / 64)
    ramp = np.clip((np.arange(32) - 10) / (23 - 10), 0, 1)   # lo 10, hi 23
    np.testing.assert_allclose(got, f * (1 - ramp) + f / 40 * ramp,
                               rtol=1e-5)
    shape = dict(qk_rope_head_dim=64, rope_theta=10000,
                 rope_scaling=latent_moe.YARN)
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(shape)), got,
                               rtol=1e-6)
    m = 0.1 * np.log(40.0) + 1.0
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)


# ---- the choice of experts -------------------------------------------------

def brute_force_choice(scores, bias, k, n_group, topk_group):
    """The published rule, one row at a time, in numpy; the lower index
    wins a tie."""
    biased = scores + bias
    chosen = []
    for row in biased:
        groups = row.reshape(n_group, -1)
        gscore = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-gscore, kind='stable')[:topk_group]
        masked = np.full_like(row, -np.inf)
        for g in kept:
            lo = g * groups.shape[1]
            masked[lo:lo + groups.shape[1]] = row[lo:lo + groups.shape[1]]
        chosen.append(np.argsort(-masked, kind='stable')[:k])
    return np.asarray(chosen)


@pytest.mark.parametrize('case', ['random', 'ties', 'bias_decides'])
def test_group_limited_choice_matches_a_brute_force_choice(case):
    rng = np.random.RandomState(5)
    e, h, k, groups, kept = 32, 16, 4, 8, 3
    hid = rng.randn(24, h).astype(np.float32)
    router = rng.randn(e, h).astype(np.float32)
    bias = (0.02 * rng.randn(e)).astype(np.float32)
    if case == 'ties':
        router[1::2] = router[0::2]         # pairs of equal scores
        bias[:] = 0.0
    if case == 'bias_decides':
        router[1::2] = router[0::2]
        bias = np.where(np.arange(e) % 2 == 1, 1e-3, 0.0).astype(np.float32)
    kw = dict(top_k=k, n_group=groups, topk_group=kept, scale=2.5)
    chosen, w = rexp.route(jnp.asarray(hid), jnp.asarray(router),
                           jnp.asarray(bias), **kw)
    scores = np.asarray(jax.nn.sigmoid(jnp.asarray(hid) @ jnp.asarray(
        router).T))
    want = brute_force_choice(scores, bias, k, groups, kept)
    np.testing.assert_array_equal(np.asarray(chosen), want)
    # weights: the scores WITHOUT the bias, normalised over all chosen
    picked = np.take_along_axis(scores, want, axis=1)
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * picked / picked.sum(axis=1, keepdims=True),
        rtol=1e-5)
    ref_chosen, ref_w = ref.route(
        jnp.asarray(hid), jnp.asarray(router), jnp.asarray(bias),
        dict(num_experts_per_tok=k, n_group=groups, topk_group=kept,
             routed_scaling_factor=2.5))
    np.testing.assert_array_equal(np.asarray(ref_chosen), want)
    np.testing.assert_allclose(np.asarray(ref_w), np.asarray(w), rtol=1e-6)
    if case == 'bias_decides':
        assert np.all(want[:, 0] % 2 == 1)   # the biased twin comes first


# ---- the shares of an expert-parallel layer --------------------------------

LAYER_KW = dict(top_k=4, n_group=4, topk_group=2, scale=2.5)


def layer_params(rng, e_all, first, count, h=32, f=16):
    """One routed layer's weights with experts ``first..first+count`` of
    ``e_all`` held (every share draws from the same full set)."""
    full = {'gate': rng.randn(e_all, h, f), 'up': rng.randn(e_all, h, f),
            'down': rng.randn(e_all, f, h)}
    cut = lambda a: jnp.asarray(a[first:first + count] * 0.2, jnp.float32)
    return {'experts': {k: cut(v) for k, v in full.items()}}


def test_the_shares_add_up_to_the_uncut_layer():
    """The partial results of held = 8j..8j+7 over all j, with the shared
    expert counted once, are the uncut reference layer."""
    e_all, per, h, f, t = 32, 8, 32, 16, 20
    rng = np.random.RandomState(1)
    hid = jnp.asarray(rng.randn(t, h), jnp.float32)
    common = {
        'router': jnp.asarray(rng.randn(e_all, h), jnp.float32),
        'router_bias': jnp.asarray(0.02 * rng.randn(e_all), jnp.float32),
        'shared': {'gate': jnp.asarray(rng.randn(h, f) * 0.2, jnp.float32),
                   'up': jnp.asarray(rng.randn(h, f) * 0.2, jnp.float32),
                   'down': jnp.asarray(rng.randn(f, h) * 0.2, jnp.float32)}}
    shape = dict(num_experts_per_tok=4, n_group=4, topk_group=2,
                 routed_scaling_factor=2.5, n_routed_experts=e_all)
    whole = dict(common, **layer_params(np.random.RandomState(2), e_all, 0,
                                        e_all))
    want = ref.routed_experts(whole, hid, shape)
    shared = rexp.swiglu(common['shared'], hid, jnp.float32)
    total, held_rows = shared, 0
    for j in range(e_all // per):
        lp = dict(common, **layer_params(np.random.RandomState(2), e_all,
                                         j * per, per))
        y, counts = rexp.routed_experts(lp, hid, jnp.ones((t,), bool),
                                        held=(j * per, per), **LAYER_KW)
        total = total + (y - shared)
        held_rows += int(counts[1])
        # the reference given the same share gives the same part
        part = ref.routed_experts(lp, hid, dict(
            shape, n_routed_experts=per, held_first=j * per,
            router_width=e_all))
        np.testing.assert_allclose(y, part, atol=2e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert held_rows == t * 4            # every choice met exactly one share


@pytest.mark.parametrize('t', [40, 300])     # tiles of 16 rows, and of 128
def test_no_row_is_dropped_when_every_row_goes_to_one_expert(t):
    e_all, h, f = 16, 32, 16
    rng = np.random.RandomState(3)
    hid = jnp.asarray(np.abs(rng.randn(t, h)) + 0.5, jnp.float32)
    router = np.zeros((e_all, h), np.float32)
    router[5] = 1.0                       # expert 5 scores highest, always
    router[6], router[7], router[4] = 0.5, 0.25, 0.1
    lp = dict(layer_params(rng, e_all, 4, 4, h, f),
              router=jnp.asarray(router),
              router_bias=jnp.zeros((e_all,), jnp.float32),
              shared={k: jnp.zeros(s, jnp.float32) for k, s in (
                  ('gate', (h, f)), ('up', (h, f)), ('down', (f, h)))})
    y, counts = rexp.routed_experts(lp, hid, jnp.ones((t,), bool),
                                    held=(4, 4), **LAYER_KW)
    chosen, w = rexp.route(hid, lp['router'], lp['router_bias'], **LAYER_KW)
    assert np.all(np.sort(np.asarray(chosen), axis=1) == [4, 5, 6, 7])
    want = jnp.zeros_like(hid)
    for e in range(4):
        w_e = jnp.sum(jnp.where(chosen == 4 + e, w, 0.0), axis=-1)
        want = want + w_e[:, None] * rexp.swiglu(
            {k: v[e] for k, v in lp['experts'].items()}, hid, jnp.float32)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    counted = dict(zip(rexp.COUNTS, (int(x) for x in counts)))
    assert counted == {'rows_offered': 4 * t, 'rows_held': 4 * t,
                       'expert_calls': 4, 'experts_touched': 4,
                       'group_rows_max': t}


# ---- the kernels, interpreted, against jax.numpy ---------------------------

@pytest.mark.parametrize('pos', [
    # one row past a page, a page's last row, deep in the third page, an
    # idle slot (its table all trash, its one step over the trash page)
    (200, 127, 300, 0),
    (300, 0, 130),              # an idle slot between two busy ones
    (127, 128, 0, 255, 256),    # rows on a page's edge
    (383, 383),                 # every slot full: the dense grid
    (0, 0, 0),                  # no slot busy
], ids=lambda pos: '_'.join(map(str, pos)))
@pytest.mark.parametrize('dtype,tol', [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_paged_latent_kernel_matches_the_gathered_attention(interpret, dtype,
                                                            tol, pos):
    rng = np.random.RandomState(0)
    layers, ps, w, rank, heads, p_max = 2, 128, 256, 128, 8, 3
    slots = len(pos)
    n = 1 + slots * p_max
    pool = jnp.asarray(rng.randn(layers, n, ps, w), dtype)
    q = jnp.asarray(rng.randn(slots, heads, w), dtype)
    # the pages a slot holds, in any order; the rest of its row is trash
    free = iter(rng.permutation(np.arange(1, n)))
    table = np.zeros((slots, p_max), np.int32)
    for i, p in enumerate(pos):
        if p:
            table[i, :p // ps + 1] = [next(free) for _ in range(p // ps + 1)]
    table, pos = jnp.asarray(table), jnp.asarray(pos, jnp.int32)
    assert pla.paged_latent_attention_available(q, pool)
    for layer in range(layers):
        got = pla.paged_latent_attention(q, pool, table, pos, layer,
                                         scale=0.3, rank=rank)
        want = pla.paged_latent_attention_fallback(q, pool, table, pos,
                                                   layer, 0.3, rank)
        assert got.shape == (slots, heads, rank)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize('sizes', [
    [3, 0, 17, 1], [0, 0, 0, 0], [0, 40, 0, 0], [16, 16, 16, 16]])
def test_grouped_expert_kernel_matches_each_groups_product(interpret, sizes):
    rng = np.random.RandomState(1)
    tm, k, n, e = 16, 128, 256, len(sizes)
    tiles = [-(-s // tm) for s in sizes]
    m = (sum(tiles) + 3) * tm                # three tiles of padding
    rows = np.zeros((m, k), np.float32)
    tile_expert, at = [], 0
    for ex, (s, nt) in enumerate(zip(sizes, tiles)):
        rows[at:at + s] = rng.randn(s, k)
        tile_expert += [ex] * nt
        at += nt * tm
    n_tiles = len(tile_expert)
    tile_expert = jnp.asarray(tile_expert + [e - 1] * (m // tm - n_tiles),
                              jnp.int32)
    w = jnp.asarray(rng.randn(e, k, n) * 0.1, jnp.float32)
    rows = jnp.asarray(rows)
    assert gmm.expert_grouped_matmul_available(rows, w, tm)
    got = gmm.expert_grouped_matmul(rows, w, tile_expert, n_tiles, tm=tm)
    want = gmm.expert_grouped_matmul_fallback(
        rows, w, tile_expert, jnp.asarray(n_tiles), tm)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert not np.any(np.asarray(got[n_tiles * tm:]))   # padding: zeros
    at = 0
    for ex, (s, nt) in enumerate(zip(sizes, tiles)):
        np.testing.assert_allclose(got[at:at + s], rows[at:at + s] @ w[ex],
                                   atol=1e-4)
        at += nt * tm


def test_prefill_attention_at_two_widths_matches_plain_softmax(interpret):
    rng = np.random.RandomState(2)
    b, t, h, dqk, dv = 1, 256, 2, 24, 16
    q, k = (jnp.asarray(rng.randn(b, t, h, dqk), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, t, h, dv), jnp.float32)
    got = pla.latent_prefill_attention(q, k, v, scale=0.2)
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * 0.2
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    want = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, axis=-1), v)
    assert got.shape == (b, t, h, dv)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_paged_write_takes_a_plane_without_a_heads_axis():
    pages = jnp.zeros((4, 8, 6), jnp.float32)
    rows = jnp.arange(2 * 3 * 6, dtype=jnp.float32).reshape(2, 3, 6)
    table = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    out = paged_kv.paged_write(pages, rows, table,
                               jnp.asarray([7, 0], jnp.int32),
                               jnp.asarray([3, 2], jnp.int32))
    np.testing.assert_array_equal(out[1, 7], rows[0, 0])    # row 7: page 1
    np.testing.assert_array_equal(out[2, 0], rows[0, 1])    # row 8: page 2
    np.testing.assert_array_equal(out[3, 1], rows[1, 1])
    np.testing.assert_array_equal(out[0, 2], rows[1, 2])    # padding: trash
    assert not np.any(np.asarray(out[3, 2:]))


# ---- the family interface --------------------------------------------------

def test_the_latent_pool_is_one_plane_and_the_engine_names_no_family():
    fam = family.family_of(config_of(SHAPE))
    assert fam.name == 'latent_moe' and not fam.tail_prefill
    pool = fam.init_pool(config_of(SHAPE), 7, 16)
    assert {k: v.shape for k, v in pool.items()} == {
        'latent': (3, 7, 16, 256)}           # 128 + 8 values: two lanes
    copied = paged_kv.copy_page(
        jax.tree_util.tree_map(lambda a: a.at[:, 2].set(1.0), pool), 2, 5)
    assert np.all(np.asarray(copied['latent'][:, 5]) == 1.0)
    with pytest.raises(TypeError, match='no generation family'):
        family.family_of(object())
    src = open(os.path.join(REPO, 'paddle_tpu', 'serving',
                            'generation.py')).read()
    assert "'moe' in" not in src and 'init_paged_kv_cache' not in src


# ---- the counters ----------------------------------------------------------

def test_moe_counters_count_what_a_hand_made_batch_routes(weights):
    """Three rows, one of them padding: what the layers count is what the
    reference's own choice says, and ``note_counts`` adds it to moe.*."""
    cfg, params = config_of(SHAPE), weights
    toks = jnp.asarray([[5, 9, 0]], jnp.int32)
    pool = latent_moe.init_pool(cfg, 3, 16)
    cache = dict(pool, page_table=jnp.asarray([[1]], jnp.int32),
                 valid=jnp.asarray([2], jnp.int32))
    _, out = latent_moe.forward_with_cache(
        params, toks, cache, jnp.zeros((1,), jnp.int32), cfg)
    got = dict(zip(rexp.COUNTS, (int(x) for x in out['counts'])))
    # by hand: run the reference layer by layer and look at its choices
    x = ref.embed(weights, toks[:, :2])
    offered = held = touched = biggest = 0
    for lp in weights['layers']:
        if 'router' in lp:
            y = ref.rms(x + ref.attention(
                lp, ref.rms(x, lp['attn_norm'], 1e-6), SHAPE),
                lp['ffn_norm'], 1e-6)
            chosen = np.asarray(ref.route(y, lp['router'],
                                          lp['router_bias'], SHAPE)[0])
            local = chosen[(chosen >= 4) & (chosen < 8)]
            sizes = np.bincount(local - 4, minlength=4)
            offered += chosen.size
            held += local.size
            touched += int(np.sum(sizes > 0))
            biggest = max(biggest, int(sizes.max()))
        x = ref.layer(lp, x, SHAPE)
    assert got == {'rows_offered': offered, 'rows_held': held,
                   'expert_calls': 2 * 4, 'experts_touched': touched,
                   'group_rows_max': biggest}
    assert offered == 2 * 2 * 4          # two rows, two layers, four each
    before = {n: getattr(obs.find(f'moe.{n}_total', {'phase': 'prefill'}),
                         'value', 0) for n in rexp.COUNTS[:4]}
    latent_moe.note_counts(np.asarray(out['counts']), 'prefill')
    for n in rexp.COUNTS[:4]:
        assert obs.find(f'moe.{n}_total',
                        {'phase': 'prefill'}).value - before[n] == got[n]
    assert obs.find('moe.group_rows_max', {'phase': 'prefill'}).count >= 1
