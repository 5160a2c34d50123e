"""The window-and-full-attention family (models/afmoe.py) and what it
forced: a window in the flash forward and in the paged decode kernel, a
page pool of two kinds of plane whose window kind gives back what left the
window (serving/generation.py), the routed layer told which experts it
holds. Small sizes on the CPU: a window of 8 rows over pages of 4 through
the jnp paths, and the kernels themselves through the Pallas interpreter.
The engine's contract is tests/family_contract.py's, bound to this family's
row of tests/served_families.py; what stays here is the family's own."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import afmoe
from paddle_tpu.parallel import routed_experts as rex
from paddle_tpu.serving import GenerationEngine

from family_contract import Contract, borrow, served_of
from served_families import FAMILIES, FULL, SLIDING

pytestmark = pytest.mark.gen
fa = importlib.import_module('paddle_tpu.ops.flash_attention')
pa = importlib.import_module('paddle_tpu.ops.paged_attention')
row = FAMILIES['afmoe']
served = served_of(row)
ref = row.ref
tiny_shape, program_config = row.shape, row.config
ENGINE = row.engine


def f32_params(shape):
    """The reference's float32 weights, which the program takes as they
    are: the shared ones for the row's own shape."""
    return (served.stacked if shape == tiny_shape()
            else row.weights(shape)[1])


class TestAfmoeContract(Contract):
    row = FAMILIES['afmoe']


@pytest.fixture
def interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


# ---- the windowed flash forward --------------------------------------------

def _brute_attention(q, k, v, window, q_pos, k_pos):
    """q [B, Tq, H, D] at positions q_pos [B, Tq]; k, v [B, Tk, Hkv, D] at
    k_pos [Tk]: row i sees keys q_pos - window < k_pos <= q_pos."""
    g = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, g, axis=2) for a in (k, v))
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) / np.sqrt(q.shape[-1])
    seen = k_pos[None, None, :] <= q_pos[:, :, None]
    if window is not None:
        seen &= k_pos[None, None, :] > q_pos[:, :, None] - window
    s = jnp.where(seen[:, None], s, -jnp.inf)
    return jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize('s,window,heads,kv_heads', [
    (512, 128, 6, 1),       # one block: the window inside the diagonal tile
    (512, 129, 6, 1),
    (1024, 512, 6, 1),      # the lower edge on a block's first row
    (1024, 513, 6, 1),      # ... and one past it
    (1024, 300, 2, 2),      # a window shorter than a block
    (1536, 512, 6, 1),      # tiles wholly before the window are skipped
    (1100, 256, 2, 1),      # a cut last k/v block, blocks of 128
    (2048, 1024, 2, 1),
])
def test_windowed_flash_forward_matches_brute_force(interpret, s, window,
                                                    heads, kv_heads):
    keys = jax.random.split(jax.random.PRNGKey(s + window), 3)
    q = jax.random.normal(keys[0], (1, s, heads, 128), jnp.float32)
    k, v = (jax.random.normal(kk, (1, s, kv_heads, 128), jnp.float32)
            for kk in keys[1:])
    out = fa.flash_attention(q, k, v, causal=True, window=window)
    pos = jnp.arange(s)
    want = _brute_attention(q, k, v, window, pos[None], pos)
    np.testing.assert_allclose(out, want, atol=3e-6, rtol=0)


def test_the_tile_plan_skips_what_lies_before_the_window():
    """16,384 rows in blocks of 512 under a window of 4,096: a q block
    visits its diagonal tile, 7 whole tiles and the one the window's lower
    edge crosses; the 300 tiles before that are not visited."""
    plan = fa.causal_tile_plan(16384, 16384, 512, 512, window=4096)['fwd']
    full = fa.causal_tile_plan(16384, 16384, 512, 512)['fwd']
    assert full['interior'] == 32 * 31 // 2 and full['diagonal'] == 32
    assert plan['diagonal'] == 32 and plan['sub'] == full['sub']
    assert plan['window_edge'] == 32 - 8
    assert plan['window_skipped'] == sum(range(32 - 8))
    assert (plan['interior'] + plan['window_edge'] + plan['window_skipped']
            == full['interior'])
    assert plan['interior'] == sum(min(i, 7) for i in range(32))
    # the plan of a call without a window is what it was
    assert set(fa.causal_tile_plan(1024, 1024, 512, 512)) == {
        'fwd', 'dq', 'dkv'}


def test_a_window_is_a_forward_only(interpret):
    q = jnp.ones((1, 256, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match='causal'):
        fa.flash_attention(q, q, q, causal=False, window=8)
    with pytest.raises(NotImplementedError, match='window'):
        jax.grad(lambda x: jnp.sum(fa.flash_attention(
            x, q, q, causal=True, window=128)))(q)


# ---- the windowed paged kernel ---------------------------------------------

def _paged_case(positions, window, t=1, groups=6, seed=0, p_max=8):
    """Slots at ``positions`` over pages of 128: a table that names ONLY
    the pages the window still reads (what the engine leaves a slot), the
    rest 0, an idle slot where the position is None."""
    ps, d, hkv = 128, 128, 2
    b = len(positions)
    rng = np.random.RandomState(seed)
    n = 1 + b * p_max
    k, v = (jnp.asarray(rng.randn(n, hkv, ps, d), jnp.float32)
            for _ in range(2))
    pos = np.array([p or 0 for p in positions], np.int32)
    table = np.zeros((b, p_max), np.int32)
    for i, p in enumerate(positions):
        if p is None:
            continue
        first = max(0, p - window + 1) // ps if window else 0
        for page in range(first, (p + t - 1) // ps + 1):
            table[i, page] = 1 + i * p_max + page
    q = jnp.asarray(rng.randn(b, t, hkv * groups, d), jnp.float32)
    # the brute force reads the rows through the FULL table
    full = 1 + np.arange(b)[:, None] * p_max + np.arange(p_max)[None]
    rows = lambda a: jnp.moveaxis(a[full], 2, 3).reshape(
        b, p_max * ps, hkv, d)
    q_pos = jnp.asarray(pos)[:, None] + jnp.arange(t)[None]
    want = _brute_attention(q, rows(k), rows(v), window, q_pos,
                            jnp.arange(p_max * ps))
    return q, k, v, jnp.asarray(table), jnp.asarray(pos), want


@pytest.mark.parametrize('positions,window,t', [
    # the window's first row on a page's first row (pos - 299 = 128, 256)
    # and on its last (127, 255); inside the window; an idle slot
    ([427, 555, 426, 554], 300, 1),
    ([0, 299, 300, None], 300, 1),
    ([1000, 127, 128, 383], 300, 1),
    ([900, None, 1023, 511], 256, 1),       # a window of whole pages
    ([500, 130, 700, 3], 300, 5),           # a tail call's rows
])
def test_windowed_paged_kernel_matches_brute_force(interpret, positions,
                                                   window, t):
    q, k, v, table, pos, want = _paged_case(positions, window, t)
    assert pa.paged_attention_available(q, k)
    out = pa.paged_flash_decode(q, k, v, table, pos, window=window)
    live = [i for i, p in enumerate(positions) if p is not None]
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live],
                               atol=2e-6, rtol=0)
    gathered = pa.paged_attention_fallback(q, k, v, table, pos, jnp.float32,
                                           window=window)
    np.testing.assert_allclose(np.asarray(gathered)[live],
                               np.asarray(want)[live], atol=2e-6, rtol=0)


@pytest.mark.parametrize('window,walked,longest', [
    # slot 0 at row 1,000: pages 5, 6, 7 hold its last 300 rows, pages
    # 0..7 all of them; slot 1 at row 5 holds one page either way
    (300, [(0, 5), (0, 6), (0, 7), (1, 0)], 2 * 4),
    (None, [(0, p) for p in range(8)] + [(1, 0)], 2 * 16),
])
def test_the_window_call_walks_a_window_of_pages_not_the_table(
        interpret, window, walked, longest):
    """A window call walks at most ``window_pages`` a slot, from the first
    page that holds a key of the window, a full call what the slot holds;
    neither walks the table's width. The grid's bound is the schedule's
    length, read on the device, and the window call carries a name of its
    own."""
    assert pa.window_pages(4096, 128) == 33 and pa.window_pages(8, 4) == 3
    assert pa.window_pages(300, 128) == 4 and pa.window_pages(256, 128) == 3
    q, k, v, table, pos, _ = _paged_case([1000, 5], 300, p_max=16)
    slot, page, total = pa.page_schedule(pos, 1, 128, 16, window)
    assert slot.shape == page.shape == (longest,)
    assert int(total) == len(walked)
    assert list(zip(np.asarray(slot)[:int(total)].tolist(),
                    np.asarray(page)[:int(total)].tolist())) == walked
    text = str(jax.make_jaxpr(lambda *a: pa.paged_flash_decode(
        *a, window=window))(q, k, v, table, pos))
    assert 'grid=(1, DynamicGridDim)' in text
    assert ('name=paged_attention_window' in text) == (window is not None)
    assert 'name=paged_attention' in text


# ---- the pool's two kinds of plane -----------------------------------------

def _watched_engine(shape, **kw):
    """An engine whose every decode step first checks the allocator's
    invariants: a slot holds at most ``window_pages`` pages of the window
    kind, exactly those its next step reads, and a kind's allocator counts
    what the slots' tables name. Of the standard geometry it calls the
    standard run's executables."""
    eng = GenerationEngine(f32_params(shape), program_config(shape), **kw)
    if {k: v for k, v in kw.items() if k != 'autostart'} == ENGINE:
        borrow(eng, served.standard.engine)
    seen = {'most': 0, 'steps': 0}
    ensure = eng._ensure_pages_locked
    ps, w = eng.page_size, shape['sliding_window']

    def checked():
        ensure()
        held = {name: 0 for name in eng._allocs}
        for slot in eng._slots:
            if slot is None:
                continue
            for name, table in slot.tables.items():
                mine = np.flatnonzero(table)
                held[name] += len(mine)
                if not eng._steps_on(slot):
                    # its last step is in flight: it holds what that step
                    # reads until its token is read, and asks for no more
                    continue
                if name == 'window':
                    seen['most'] = max(seen['most'], len(mine))
                    first = max(0, slot.pos - w + 1) // ps
                    assert list(mine) == list(range(first,
                                                    slot.pos // ps + 1))
                else:
                    assert list(mine) == list(range(slot.pos // ps + 1))
        for name, alloc in eng._allocs.items():
            assert alloc.used_pages == held[name]
        seen['steps'] += 1
    eng._ensure_pages_locked = checked
    return eng, seen


@pytest.mark.parametrize('pages,evicts', [
    (None, False),                              # a window's pages a slot
    ({'full': 25, 'window': 7}, True),          # neither kind holds 3 slots
    ({'full': 97, 'window': 5}, True),          # the window kind alone short:
                                                # a short prompt's slot grows
])
def test_a_window_kind_holds_a_windows_pages_and_gives_back_the_rest(
        pages, evicts):
    shape = tiny_shape()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 96, size=n).astype(np.int32)
               for n in (30, 3, 38, 2, 26)]
    eng, seen = _watched_engine(shape, **ENGINE, **(
        {'num_pages': pages} if pages else {}))
    assert eng._held_max == {'full': 16, 'window': 3}
    released = obs.find('kv.pages_released_total',
                        {**eng.labels, 'kind': 'window'})
    with eng:
        futs = [eng.submit(p, max_new_tokens=24) for p in prompts]
        got = [f.result(timeout=600) for f in futs]
        stats = eng.stats()
    assert seen['steps'] > 24 and seen['most'] == 3     # ceil(8 / 4) + 1
    assert (stats['evictions'] > 0) is evicts
    # every page is free again, and every page given back was reused at
    # once: the window kind served 5 requests of up to 62 rows from 3 (or
    # fewer) slots' worth of pages
    assert stats['free_pages'] == stats['num_pages'] - 2
    first = lambda pos: max(0, pos - 8 + 1) // 4        # noqa: E731
    assert released.value >= sum(first(len(p) + 22) - first(len(p))
                                 for p in prompts) > 25
    # eviction and requeue change no token: the same as from a pool that
    # never runs short
    if evicts:
        roomy, _ = _watched_engine(shape, **ENGINE)
        with roomy:
            want = [roomy.submit(p, max_new_tokens=24).result(timeout=600)
                    for p in prompts]
        assert got == want


@pytest.mark.parametrize('lens,slots,rows_equal', [
    # a request a slot, queued before the engine starts: every step holds
    # the same rows in both orders, so the routed layers group them alike
    ((30, 3, 38), 3, True),
    # five requests on three slots: a slot changes hands with a step in
    # flight, and a neighbour's row then meets another group by a step
    ((30, 3, 38, 2, 26), 3, False),
], ids=['a_request_a_slot', 'slots_refilled'])
def test_one_step_ahead_gives_back_the_pages_reading_first_gives_back(
        lens, slots, rows_equal, read_first):
    """The decode loop dispatches step N+1 before it reads step N (PR 36):
    a window kind gives back the pages that step N+1 no longer reads while
    step N, which still reads them, is in flight, and whoever takes them
    writes them behind it. Beside the contract's case (the same tokens and
    rows in both orders): the allocator's invariants hold at every step of
    both, and both give back the same pages."""
    shape = tiny_shape()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 96, size=n).astype(np.int32) for n in lens]

    def serve():
        eng, seen = _watched_engine(shape, **dict(ENGINE, num_slots=slots),
                                    autostart=False)
        released = obs.find('kv.pages_released_total',
                            {**eng.labels, 'kind': 'window'})
        futs = [eng.submit(p, max_new_tokens=12 + 4 * i, want_logits=True)
                for i, p in enumerate(prompts)]
        with eng:
            out = [(f.result(timeout=600), np.stack(f.logits()))
                   for f in futs]
            return out, eng.stats(), released.value

    got, stats, released = serve()
    with read_first():
        want, base, released_first = serve()
    assert base['steps_overlapped'] == 0 < stats['steps_overlapped']
    assert released == released_first >= 8      # pages left the windows
    assert stats['free_pages'] == stats['num_pages'] - 2
    for (toks, rows), (want_toks, want_rows) in zip(got, want):
        assert toks == want_toks
        if rows_equal:
            np.testing.assert_array_equal(rows, want_rows)
        else:
            np.testing.assert_allclose(rows, want_rows, atol=2e-5, rtol=0)


def test_a_family_of_kinds_gets_no_prefix_cache_and_names_its_pools():
    shape = tiny_shape()
    with pytest.raises(ValueError, match='prefix cache'):
        GenerationEngine(f32_params(shape), program_config(shape),
                         prefix_cache=True, autostart=False)
    with pytest.raises(ValueError, match='kinds'):
        GenerationEngine(f32_params(shape), program_config(shape),
                         num_pages={'kv': 9}, autostart=False)
    eng = GenerationEngine(f32_params(shape), program_config(shape),
                           num_slots=2, page_size=4, autostart=False)
    assert eng._num_pages == {'full': 2 * 16 + 1, 'window': 2 * 3 + 1}
    assert {k: v.shape for k, v in eng._pool.items()} == {
        'k_full': (1, 33, 1, 4, 8), 'v_full': (1, 33, 1, 4, 8),
        'k_window': (4, 7, 1, 4, 8), 'v_window': (4, 7, 1, 4, 8)}
    assert set(eng._tables(2)) == {'full', 'window'}
    eng.shutdown()


# ---- the routed layer's share, positions, counters -------------------------

def test_the_shares_of_every_chip_add_up_to_the_uncut_layer():
    """Four chips hold experts (4 j, 4) of 16; each computes its experts'
    weighted part and the shared expert. The parts, with the shared expert
    counted once, are the whole layer's output, in program and reference."""
    whole = tiny_shape(num_experts=16, router_width=16,
                       num_experts_per_tok=4)
    key = jax.random.PRNGKey(11)
    h = jax.random.normal(jax.random.PRNGKey(2), (24, 32), jnp.float32)
    ok = jnp.ones((24,), bool)
    f32 = lambda t: jax.tree_util.tree_map(          # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    route = dict(top_k=4, n_group=1, topk_group=1, scale=2.448,
                 normalise=True)
    lp = f32(ref.init_layer(whole, key, 2))
    uncut, _ = rex.routed_experts(lp, h, ok, held=(0, 16), **route)
    shared = rex.swiglu(lp['shared'], h, jnp.float32)
    parts, ref_parts = [], []
    for j in range(4):
        share = dict(whole, num_experts=4, held_first=4 * j)
        lp_j = f32(ref.init_layer(share, key, 2))
        np.testing.assert_array_equal(lp_j['experts']['up'],
                                      lp['experts']['up'][4 * j:4 * j + 4])
        y, counts = rex.routed_experts(lp_j, h, ok, held=(4 * j, 4), **route)
        parts.append(y - shared)
        assert int(counts[0]) == 24 * 4 and int(counts[2]) == 4
        with jax.default_matmul_precision('highest'):
            ref_parts.append(ref.routed_experts(lp_j, h, share) - shared)
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=2e-5)
    np.testing.assert_allclose(sum(ref_parts) + shared, uncut, atol=2e-5)


@pytest.mark.parametrize('kind,moves', [(FULL, False), (SLIDING, True)])
def test_full_layers_ignore_positions_and_window_layers_do_not(kind, moves):
    """One layer, a window longer than the prompt: the last row attends
    every row. Without positions it reads them as a set, so the order of
    the earlier tokens does not reach it; with rotary positions it does."""
    shape = tiny_shape(num_hidden_layers=1, num_dense_layers=1,
                       layer_types=[kind], sliding_window=64)
    cfg, params = program_config(shape), f32_params(shape)
    tokens = np.arange(3, 19, dtype=np.int32)
    swapped = tokens.copy()
    swapped[[2, 9]] = swapped[[9, 2]]
    for forward in (lambda t: afmoe.forward(params, t, cfg),
                    lambda t: ref.forward(params, t, shape)):
        a, b = (np.asarray(forward(jnp.asarray(t)[None]))[0, -1]
                for t in (tokens, swapped))
        assert bool(np.max(np.abs(a - b)) > 1e-3) is moves
        if not moves:
            np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_step_counts_what_each_kind_of_layer_attended():
    """``forward_with_cache`` counts, on the device, the keys and pages one
    full layer and one window layer of a decode step read; ``note_counts``
    takes them to ``attn.*_attended_total`` by kind, a prefill's to none."""
    shape = tiny_shape()
    cfg, params = program_config(shape), f32_params(shape)
    pool = afmoe.init_pool(cfg, {'full': 33, 'window': 33}, 4)
    table = jnp.arange(1, 33, dtype=jnp.int32).reshape(2, 16)
    cache = dict(pool, page_table={'full': table, 'window': table})
    pos = jnp.asarray([5, 30], jnp.int32)
    _, out = afmoe.forward_with_cache(
        params, jnp.zeros((2, 1), jnp.int32), cache, pos, cfg)
    counts = np.asarray(out['counts'])
    assert len(counts) == len(rex.COUNTS) + len(afmoe.ATTENDED)
    # keys: 6 + 31 against 6 + 8; pages of 4 rows: 2 + 8 against 2 + 3
    assert list(counts[len(rex.COUNTS):]) == [37, 14, 10, 5]
    before = {k: getattr(obs.find(f'attn.{w}_attended_total', {'kind': k}),
                         'value', 0)
              for k in ('full', 'window') for w in ('keys',)}
    afmoe.note_counts(counts, 'prefill')
    afmoe.note_counts(counts, 'decode')
    for kind, add in (('full', 37), ('window', 14)):
        assert obs.find('attn.keys_attended_total',
                        {'kind': kind}).value == before[kind] + add
    # a prefill's count leaves the attention's entries zero
    _, out = afmoe.forward_with_cache(
        params, jnp.zeros((2, 4), jnp.int32),
        dict(cache, valid=jnp.asarray([4, 3])), jnp.zeros((2,), jnp.int32),
        cfg, last_only=True)
    assert list(np.asarray(out['counts'])[len(rex.COUNTS):]) == [0, 0, 0, 0]


@pytest.mark.parametrize('piece,calls', [
    (16, 3),        # 48 rows in three whole pieces
    (20, 3),        # two whole pieces and a last one of 8 rows
    (32, 2),        # one whole piece and a last one of 16
    (40, 2)])       # one whole piece and a last one of 8
def test_a_long_prefill_takes_its_mlp_half_in_pieces(monkeypatch, piece,
                                                     calls):
    """Past ``MLP_ROWS`` rows the MLP half runs a piece at a time, and a
    width that is no multiple of it (6,144 of the engine's widths over
    pieces of 4,096) leaves a last, shorter piece:
    the same rows as the unpieced half, the routed layers' counts summed
    (the largest group: the largest of any piece)."""
    shape = tiny_shape(max_position_embeddings=64)
    cfg, params = program_config(shape), f32_params(shape)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 96, (1, 48)))

    def run():
        pool = afmoe.init_pool(cfg, {'full': 3, 'window': 3}, 48)
        cache = dict(pool, page_table={k: jnp.ones((1, 2), jnp.int32)
                                       for k in ('full', 'window')})
        logits, out = afmoe.forward_with_cache(
            params, tokens, cache, jnp.zeros((1,), jnp.int32), cfg)
        return np.asarray(logits), np.asarray(out['counts'])
    whole, counts = run()
    monkeypatch.setattr(afmoe, 'MLP_ROWS', piece)
    pieces, piece_counts = run()
    np.testing.assert_allclose(pieces, whole, atol=1e-5)
    # the same rows offered and held; a piece is a call of its own, which
    # offers the held experts again
    assert list(piece_counts[:2]) == list(counts[:2])
    assert piece_counts[2] == calls * counts[2]
    assert counts[3] <= piece_counts[3] <= calls * counts[3]
    assert 0 < piece_counts[4] <= counts[4]
