"""The paged KV pool below the engine (moved, bodies unchanged, from
tests/test_generation.py, whose worker they kept busy: none builds an
engine): the page allocator and the paged write, the Pallas paged-attention
kernel in interpret mode over every shape a configuration or a test hands
it, the ragged page schedule its grid walks, the decode plan that fits a
step into fast memory, and the decode-fn cache."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import DecodeFnCache, clear_decode_caches
from paddle_tpu.ops import paged_kv

# ops/__init__ rebinds `flash_attention` to the FUNCTION, shadowing the
# submodule for attribute-style imports — importlib reaches the module
fa = importlib.import_module('paddle_tpu.ops.flash_attention')
pa = importlib.import_module('paddle_tpu.ops.paged_attention')

pytestmark = pytest.mark.gen


# ---------------------------------------------------------------------------
# paged-KV plumbing
# ---------------------------------------------------------------------------

def test_pages_for_and_allocator():
    assert paged_kv.pages_for(1, 8) == 1
    assert paged_kv.pages_for(8, 8) == 1
    assert paged_kv.pages_for(9, 8) == 2
    assert paged_kv.pages_for(32, 8) == 4
    a = paged_kv.PageAllocator(5)           # page 0 reserved
    assert a.free_pages == 4
    got = a.alloc(3)
    assert got is not None and len(got) == 3
    assert paged_kv.TRASH_PAGE not in got   # trash page never handed out
    assert a.alloc(2) is None               # all-or-nothing
    assert a.free_pages == 1
    a.free(got[:2])
    assert a.free_pages == 3
    assert sorted(a.alloc(3)) == sorted(got[:2] + [4]) or a.free_pages == 0


def test_paged_write_gather_roundtrip():
    rng = np.random.RandomState(1)
    n, ps, h, d, b = 6, 4, 2, 8, 2
    pool = jnp.zeros((n, h, ps, d), jnp.float32)     # a page is head-major
    # deliberately scattered, non-contiguous physical pages
    table = jnp.asarray([[3, 1, 0, 0], [5, 2, 4, 0]], jnp.int32)
    rows = jnp.asarray(rng.randn(b, 6, h, d), jnp.float32)
    valid = jnp.asarray([5, 6], jnp.int32)   # slot 0 row 5 is padding
    pool = paged_kv.paged_write(pool, rows, table, jnp.zeros((b,), jnp.int32),
                                valid)
    virt = paged_kv.gather_virtual(pool, table)
    assert virt.shape == (b, ps * table.shape[1], h, d)
    np.testing.assert_array_equal(np.asarray(virt[0, :5]),
                                  np.asarray(rows[0, :5]))
    np.testing.assert_array_equal(np.asarray(virt[1, :6]),
                                  np.asarray(rows[1, :6]))
    # the padding row reached no page of slot 0's
    np.testing.assert_array_equal(np.asarray(virt[0, 5]),
                                  np.zeros((h, d), np.float32))


# ---------------------------------------------------------------------------
# Pallas paged-attention kernel (interpret mode)
# ---------------------------------------------------------------------------

def _kernel_setup(int8=False, seed=0, pos=(130, 200)):
    """Slots of two pages each at ``pos``; a slot at 0 is idle: its table
    names the trash page alone."""
    rng = np.random.RandomState(seed)
    b, t, h, d, ps, p_max = len(pos), 1, 2, 64, 128, 2
    n = b * p_max + 1
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32) * 0.3
    table = jnp.arange(1, n, dtype=jnp.int32).reshape(b, p_max)
    kv = [jnp.asarray(rng.randn(b, 256, h, d), jnp.float32) * 0.3
          for _ in range(2)]
    pools = []
    for rows in kv:
        pool = jnp.zeros((n, h, ps, d), jnp.float32)
        if int8:
            pool = {'int8': jnp.zeros((n, h, ps, d), jnp.int8),
                    'scale': jnp.zeros((n, h, ps), jnp.float32)}
        pools.append(paged_kv.paged_write(pool, rows, table,
                                          jnp.zeros((b,), jnp.int32)))
    pos = jnp.asarray(pos, jnp.int32)
    table = jnp.where((pos > 0)[:, None], table, paged_kv.TRASH_PAGE)
    return q, pools[0], pools[1], table, pos


@pytest.mark.parametrize('pos', [
    (130, 200),
    (200, 0, 130),          # an idle slot between two busy ones
    (127, 128),             # a page's last row, the next page's first
    (0, 128, 0, 127, 0),
], ids=lambda pos: '_'.join(map(str, pos)))
@pytest.mark.parametrize('int8', [False, True])
def test_paged_kernel_interpret_parity(int8, pos):
    q, kp, vp, table, pos = _kernel_setup(int8=int8, pos=pos)
    k_arr = kp['int8'] if int8 else kp
    fa.set_interpret(True)
    try:
        assert pa.paged_attention_available(q, k_arr)
        if int8:
            got = pa.paged_flash_decode_int8(q, kp, vp, table, pos)
        else:
            got = pa.paged_flash_decode(q, kp, vp, table, pos)
    finally:
        fa.set_interpret(False)
    want = pa.paged_attention_fallback(q, kp, vp, table, pos, jnp.float32)
    rtol = 2e-2 if int8 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=rtol, atol=rtol)


# what PR 28 changed under the kernel: a page is head-major and is read
# where it lies, for every head size; the pool comes whole (every layer's
# pages) with the table offset to one layer's. What PR 30 changed: a grid
# step takes every head of a page (fewer where ``decode_plan`` says they
# do not fit) and a KV group's query heads are rows against one K block.
# What PR 43 changed: the grid walks the pages slots hold, slot after slot
# (``page_schedule``), and no step past them
def _case(d, h, h_kv, t=1, layers=1, pos=(130, 200), p_max=2, budget=None):
    return dict(d=d, h=h, h_kv=h_kv, t=t, layers=layers, pos=pos,
                p_max=p_max, budget=budget)


_KERNEL_SHAPES = {
    'd128': _case(128, 2, 2),
    'd256': _case(256, 2, 2),
    'd64': _case(64, 2, 2),
    'gqa': _case(64, 4, 2),
    'gqa_d128': _case(128, 4, 2),
    'layer_2_of_3': _case(128, 2, 2, layers=3),
    'layer_2_of_3_d64': _case(64, 2, 2, layers=3),
    # the served shape's heads; an idle slot (pos 0, its table all trash)
    # beside a full one
    'heads16_idle_and_full': _case(128, 16, 16, pos=(0, 1023), p_max=8),
    'heads16_kv4': _case(128, 16, 4, pos=(1023, 0), p_max=8, layers=2),
    # a slot's rows end on a page's last row, or begin the next page
    'page_edges': _case(64, 2, 2, pos=(127, 128, 255)),
    'held_1_3_8_of_8': _case(128, 2, 1, pos=(100, 300, 1000), p_max=8),
    # the schedule's seams: a slot's one step over the trash page between
    # two slots' pages, at the grid's two ends, and nothing but such steps
    'idle_between_busy': _case(128, 2, 2, pos=(300, 0, 130), p_max=4),
    'idle_between_edges_gqa': _case(64, 4, 2, pos=(127, 0, 128, 0, 383),
                                    p_max=3),
    'idle_at_both_ends': _case(128, 2, 1, pos=(0, 0, 511, 0), p_max=4),
    'all_idle': _case(128, 2, 2, pos=(0, 0, 0), p_max=4),
    'tail_5_idle_between': _case(128, 4, 2, t=5, pos=(250, 0, 124),
                                 p_max=3),
    'tail_4': _case(128, 2, 2, t=4),
    'tail_5_gqa': _case(64, 4, 2, t=5, pos=(130, 3)),
    'tail_16': _case(128, 2, 2, t=16, pos=(127, 240)),
    'tail_17_gqa': _case(128, 4, 1, t=17, pos=(111, 0)),
    'tail_128': _case(64, 2, 2, t=128, pos=(0, 128)),
    # a budget that holds one KV head (of four) and two (of four) a step:
    # the grid's head-block axis, which no real shape of these sizes takes
    'head_blocks_1_of_4': _case(128, 4, 4, budget=2 ** 19),
    'head_blocks_2_of_4_gqa': _case(64, 8, 4, t=3, budget=2 ** 19 + 2 ** 18),
}
KERNEL_CASES = {f'{name}{"_int8" if int8 else ""}': dict(shape, int8=int8)
                for name, shape in _KERNEL_SHAPES.items()
                for int8 in (False, True)}


@pytest.mark.parametrize('case', sorted(KERNEL_CASES))
def test_paged_kernel_reads_pages_where_they_lie(case, monkeypatch):
    c = KERNEL_CASES[case]
    d, h, h_kv, t, int8 = c['d'], c['h'], c['h_kv'], c['t'], c['int8']
    rng = np.random.RandomState(len(case))
    b, ps, p_max = len(c['pos']), 128, c['p_max']
    n = b * p_max + 1
    pos = jnp.asarray(c['pos'], jnp.int32)
    # the pages a slot holds, in any order; the rest of its row is trash
    held = [-(-(p + t) // ps) if p or t > 1 else 0 for p in c["pos"]]
    free = iter(rng.permutation(np.arange(1, n)))
    table = np.zeros((b, p_max), np.int32)
    for i, k in enumerate(held):
        table[i, :k] = [next(free) for _ in range(k)]
    table = jnp.asarray(table + (c['layers'] - 1) * n)
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32) * 0.3
    pools = []
    for _ in range(2):      # every page holds something, the trash page too
        shape = (c['layers'] * n, h_kv, ps, d)
        if int8:
            pools.append({
                'int8': jnp.asarray(rng.randint(-127, 128, shape), jnp.int8),
                'scale': jnp.asarray(rng.uniform(1e-3, 5e-3, shape[:3]),
                                     jnp.float32)})
        else:
            pools.append(jnp.asarray(rng.randn(*shape), jnp.float32) * 0.3)
    kp, vp = pools
    if c['budget']:
        monkeypatch.setattr(pa, 'VMEM_BUDGET', c['budget'])
        plan = pa.decode_plan(h, h_kv, d, ps, t, 1 if int8 else 4, 4)
        assert plan.kv_heads < h_kv, plan
    fa.set_interpret(True)
    try:
        assert pa.paged_attention_available(q, kp['int8'] if int8 else kp)
        got = pa.paged_attention(q, kp, vp, table, pos)
    finally:
        fa.set_interpret(False)
    want = pa.paged_attention_fallback(q, kp, vp, table, pos, jnp.float32)
    tol = 2e-2 if int8 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize('window', [None, 200])
def test_paged_kernel_under_a_mesh_walks_each_devices_own_slots(window):
    """Slots split over 'dp', heads over 'mp': ``page_schedule`` is built
    inside the per-device call from the device's own positions, so the two
    halves of the batch walk grids of different lengths (3 + 1 steps and
    1 + 2 without a window) and each slot still gets its own pages."""
    from jax.sharding import Mesh
    from paddle_tpu.ops import mesh_kernel
    rng = np.random.RandomState(11)
    b, h, d, ps, p_max = 4, 4, 128, 128, 3
    pos = jnp.asarray([300, 0, 5, 250], jnp.int32)
    n = 1 + b * p_max
    table = np.arange(1, n, dtype=np.int32).reshape(b, p_max)
    table[1] = paged_kv.TRASH_PAGE
    table = jnp.asarray(table)
    q = jnp.asarray(rng.randn(b, 1, h, d), jnp.float32) * 0.3
    kp, vp = (jnp.asarray(rng.randn(n, h, ps, d), jnp.float32) * 0.3
              for _ in range(2))
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ('dp', 'mp'))
    call = mesh_kernel.jit(
        lambda *a: pa.paged_flash_decode(*a, window=window), mesh)
    fa.set_interpret(True)
    try:
        assert 'shard_map' in str(jax.make_jaxpr(call)(q, kp, vp, table, pos))
        got = call(q, kp, vp, table, pos)
    finally:
        fa.set_interpret(False)
    want = pa.paged_attention_fallback(q, kp, vp, table, pos, jnp.float32,
                                       window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize('kernel', ['paged_attention',
                                    'paged_attention_window',
                                    'paged_latent_attention'])
def test_every_index_map_clamps_the_step_to_the_schedules_last_entry(kernel):
    """Mosaic's pipeline names steps PAST a dynamic grid's bound (it looks
    ahead of the last step it runs), and an index map that read a word
    behind ``step_slot`` / ``step_page`` halted the chip with every slot
    at, or one page under, full depth (PERF.md section 6, PR 43). No run
    off the chip sees that: interpret mode evaluates no step it does not
    run. So the jaxpr is read: every block's index map takes the minimum
    of the step and the schedule's last index before it reads anything."""
    pla = importlib.import_module('paddle_tpu.ops.paged_latent_attention')
    b, p_max, ps = 3, 4, 128
    table = jnp.zeros((b, p_max), jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    fa.set_interpret(True)
    try:
        if kernel == 'paged_latent_attention':
            jaxpr = jax.make_jaxpr(lambda q, pool: pla.paged_latent_attention(
                q, pool, table, pos, 0, scale=0.1, rank=128))(
                    jnp.zeros((b, 8, 256)), jnp.zeros((1, 5, ps, 256)))
            steps = b * p_max
        else:
            window = 200 if kernel.endswith('window') else None
            jaxpr = jax.make_jaxpr(lambda q, k: pa.paged_flash_decode(
                q, k, k, table, pos, window=window))(
                    jnp.zeros((b, 1, 2, 128)), jnp.zeros((5, 2, ps, 128)))
            steps = b * (pa.window_pages(200, ps) if window else p_max)
    finally:
        fa.set_interpret(False)
    call, = [e for e in jaxpr.eqns if e.primitive.name == 'pallas_call']
    assert call.params['name'] == kernel
    maps = call.params['grid_mapping'].block_mappings
    assert len(maps) >= 3
    for m in maps:
        mins = [e for e in m.index_map_jaxpr.jaxpr.eqns
                if e.primitive.name == 'min']
        assert mins, m
        assert all(int(e.invars[1].val) == steps - 1 for e in mins), mins


def _schedule_by_hand(pos, t, ps, p_max, window):
    """(slot, page) pairs in the grid's order, from the module's words."""
    steps = []
    for i, p in enumerate(pos):
        held = min(max(-(-(p + t) // ps), 1), p_max)
        first = 0 if window is None else min(
            max(p - window + 1, 0) // ps, held - 1)
        steps += [(i, page) for page in range(first, held)]
    return steps


_SCHEDULES = {
    'two_slots': dict(pos=(130, 200), p_max=2),
    'idle_between_busy': dict(pos=(300, 0, 130), p_max=4),
    'all_idle': dict(pos=(0, 0, 0, 0), p_max=8),
    'page_edges': dict(pos=(127, 128, 255, 256), p_max=4),
    'every_slot_full': dict(pos=(1023, 1023, 1023), p_max=8),
    'past_the_table': dict(pos=(2000, 5), p_max=8),
    'tail_rows_reach_a_page': dict(pos=(120, 0, 250), p_max=4, t=16),
    'one_slot': dict(pos=(700,), p_max=8),
    'zaya_48_slots': dict(
        pos=tuple(int(x) for x in np.minimum(
            200 + np.random.RandomState(3).randint(0, 2048, 48), 3071)),
        p_max=24),
    'window_first_page_not_0': dict(pos=(1000, 5, 427, 0), p_max=16,
                                    window=300),
    'window_of_whole_pages': dict(pos=(900, 1023, 511, 255), p_max=8,
                                  window=256),
    'window_wider_than_the_table': dict(pos=(500, 100), p_max=4,
                                        window=4096),
    'window_tail_rows': dict(pos=(500, 130, 700, 3), p_max=8, window=300,
                             t=5),
}


@pytest.mark.parametrize('case', sorted(_SCHEDULES))
def test_page_schedule_lists_the_pages_slots_hold(case):
    """The grid's page axis: ``total`` is the sum of what the slots hold,
    every slot's pages come in order behind the slot's before it, an idle
    slot takes its one step over the trash page, a window's first page is
    the first that holds a key of it, and slots at full depth give the
    dense grid, slot-major."""
    c = {'t': 1, 'window': None, **_SCHEDULES[case]}
    pos, t, p_max, window = c['pos'], c['t'], c['p_max'], c['window']
    ps = 128
    slot, page, total = jax.jit(
        lambda pos: pa.page_schedule(pos, t, ps, p_max, window))(
            jnp.asarray(pos, jnp.int32))
    depth = p_max if window is None else min(
        p_max, pa.window_pages(window + t - 1, ps))
    assert slot.shape == page.shape == (len(pos) * depth,)
    assert slot.dtype == page.dtype == jnp.int32
    want = _schedule_by_hand(pos, t, ps, p_max, window)
    total = int(total)
    assert total == len(want) <= len(pos) * depth
    got = list(zip(np.asarray(slot)[:total].tolist(),
                   np.asarray(page)[:total].tolist()))
    assert got == want
    # past the steps a call walks nothing is named that is out of bounds
    assert not np.asarray(slot)[total:].any()
    assert not np.asarray(page)[total:].any()
    if case == 'every_slot_full':
        assert want == [(i, p) for i in range(len(pos))
                        for p in range(p_max)]
    if case == 'all_idle':
        assert want == [(i, 0) for i in range(len(pos))]
    if case == 'window_first_page_not_0':
        assert want[:3] == [(0, 5), (0, 6), (0, 7)] and want[3] == (1, 0)


# every shape a configuration or a test hands the kernel: head sizes 64 /
# 128 / 256, 1-32 KV heads (whole, or a quarter of them under the mesh
# engine's mp 4), groups of 1 and 4, decode steps and tails up to 128 rows
@pytest.mark.parametrize('mp', [1, 4])
@pytest.mark.parametrize('kv_itemsize,q_itemsize',
                         [(1, 2), (1, 4), (2, 2), (4, 4)])
@pytest.mark.parametrize('d', [64, 128, 256])
def test_decode_plan_fits_its_budget_and_covers_every_head(
        d, kv_itemsize, q_itemsize, mp):
    for h_kv in (1, 2, 4, 8, 16, 32):
        if h_kv % mp:
            continue
        for g in (1, 4):
            for t in (1, 2, 5, 16, 17, 128):
                for ps in (128, 256):
                    plan = pa.decode_plan(h_kv * g // mp, h_kv // mp, d, ps,
                                          t, kv_itemsize, q_itemsize)
                    assert plan is not None, (h_kv, g, t, ps)
                    assert plan.vmem_bytes <= pa.VMEM_BUDGET, plan
                    # blocks of equal size, each head in exactly one
                    assert (h_kv // mp) % plan.kv_heads == 0, plan
                    assert plan.kv_heads >= 1 and plan.in_flight == 2, plan
                    tile = 32 // q_itemsize
                    assert plan.rows % tile == 0, plan
                    assert g * t <= plan.rows < g * t + tile, plan


def test_decode_plan_of_the_served_shapes():
    """GPT-3 XL as ``gpt-1.3b-serve`` runs it (16 heads of 128, bf16 pages
    of 128 rows) takes a page whole: all 16 heads, K and V double-buffered
    2 x 2 x 512 KB, 16 q rows a head for the one that is real. So do its
    int8 banks and the mesh engine's quarter. A step of more heads than
    fit takes a divisor of them; a page of which one head does not fit has
    no plan, and the gate sends the call to the gather."""
    plan = pa.decode_plan(16, 16, 128, 128, 1, 2, 2)
    assert (plan.kv_heads, plan.rows, plan.in_flight) == (16, 16, 2)
    assert 4 * 512 * 1024 <= plan.vmem_bytes <= 3 * 2 ** 20
    assert pa.decode_plan(16, 16, 128, 128, 1, 1, 2).kv_heads == 16
    assert pa.decode_plan(4, 4, 64, 128, 1, 2, 2).kv_heads == 4
    # a 128-row tail at D 256 over 32 KV heads: q, output and state are
    # what is large, and the step takes fewer heads for them
    tail = pa.decode_plan(32, 32, 256, 128, 128, 2, 2)
    assert tail.kv_heads in (4, 8, 16) and tail.vmem_bytes <= pa.VMEM_BUDGET
    assert pa.decode_plan(2, 2, 256, 16384, 1, 2, 2) is None
    q = jnp.zeros((1, 1, 2, 256), jnp.bfloat16)
    fa.set_interpret(True)
    try:
        assert not pa.paged_attention_available(
            q, jax.ShapeDtypeStruct((3, 2, 16384, 256), jnp.bfloat16))
        assert pa.paged_attention_available(
            q, jax.ShapeDtypeStruct((3, 2, 128, 256), jnp.bfloat16))
    finally:
        fa.set_interpret(False)


@pytest.mark.parametrize('t,start', [(1, 0), (1, 7), (1, 8), (5, 6), (16, 0),
                                     (16, 3), (17, 15)])
def test_paged_write_lays_rows_over_their_pages(t, start):
    """Rows from any offset, a page at a time: what the gather gives back
    is the rows written so far and zeros, whatever pages the table names;
    rows past ``valid`` and pages past the table's end reach no page of
    the sequence, and a neighbour's pages stay as they were."""
    rng = np.random.RandomState(t * 31 + start)
    n, ps, h, d = 9, 8, 2, 4
    pool = jnp.zeros((n, h, ps, d), jnp.float32)
    table = jnp.asarray([[5, 2, 7, 0], [1, 6, 3, 8]], jnp.int32)
    before = jnp.asarray(rng.randn(2, start, h, d), jnp.float32)
    if start:
        pool = paged_kv.paged_write(pool, before, table,
                                    jnp.zeros((2,), jnp.int32))
    rows = jnp.asarray(rng.randn(2, t, h, d), jnp.float32)
    valid = jnp.asarray([max(t - 2, 1), t], jnp.int32)
    pool = paged_kv.paged_write(pool, rows, table,
                                jnp.asarray([start, start], jnp.int32),
                                valid)
    virt = np.asarray(paged_kv.gather_virtual(pool, table))
    for i in range(2):
        want = np.zeros((ps * 4, h, d), np.float32)
        want[:start] = np.asarray(before[i])
        keep = int(valid[i])
        if i == 0:
            keep = min(keep, 3 * ps - start)     # slot 0 holds three pages
        want[start:start + keep] = np.asarray(rows[i, :keep])
        if i == 0:
            want[3 * ps:] = virt[0, 3 * ps:]      # the trash page: anything
        np.testing.assert_array_equal(virt[i], want)


# ---------------------------------------------------------------------------
# decode-fn cache satellite
# ---------------------------------------------------------------------------

def test_decode_fn_cache_bounds_and_clear():
    built = []
    c = DecodeFnCache(maxsize=2, name='t')
    for key in ('a', 'b', 'a', 'c'):       # 'c' evicts LRU 'b'
        c.get(key, lambda k=key: built.append(k) or k)
    assert built == ['a', 'b', 'c']
    assert 'a' in c and 'c' in c and 'b' not in c
    assert len(c) == 2
    clear_decode_caches()
    assert len(c) == 0
    assert DecodeFnCache(maxsize=0).maxsize > 0   # 0/None -> default size
    with pytest.raises(ValueError):
        DecodeFnCache(maxsize=-1)
