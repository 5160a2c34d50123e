"""A decode step's row goes into the page pool where the pool lies
(``ops/paged_kv._row_write``, the Pallas call ``paged_row_write``): the
sublane tile the row falls in is copied in, the row laid over it and the
tile copied back, every sequence's copy in flight at once, and
``paged_write`` takes that form from what its input shows. Interpreted
here; compiled for a described chip in ``tests/test_aot_tpu_compile.py``."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_kv
from paddle_tpu.ops.weight_only import init_kv_bank

fa = importlib.import_module('paddle_tpu.ops.flash_attention')

PS, D, P_MAX = 128, 128, 2


@pytest.fixture
def interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


def _bits(x):
    return np.asarray(x).view(np.uint16 if x.dtype == jnp.bfloat16
                              else np.uint32)


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
@pytest.mark.parametrize('h_kv', [2, 4, 8, 16])
def test_the_row_kernel_writes_what_the_page_form_writes(
        interpret, h_kv, dtype, monkeypatch):
    """Every offset class of a page (its first row, the second, a tile's
    last and the next tile's first in bf16, the page's last), a row at the
    first position of a LATER page, two idle slots and a position past the
    table's end (all three name the trash page): every page but page 0
    holds, bit for bit, what ``_write_pages`` leaves, and the pages nobody
    wrote hold what they held."""
    dtype = jnp.dtype(dtype)
    if h_kv == 4:
        # the nine sequences in grid steps of three: what a step holds
        # (a tile a sequence, its row in two buffers) is held to a budget
        monkeypatch.setattr(paged_kv, '_ROW_WRITE_VMEM',
                            3 * 3 * h_kv * 32 * D)
        assert paged_kv._rows_a_step(9, h_kv, D) == 3
    offsets = [0, 1, 15, 16, 127]
    pos = offsets + [PS, 0, 0, P_MAX * PS + 3]
    b = len(pos)
    # two pages a slot, its own; the idle slots' tables name page 0, and
    # the slot past its table's end holds two pages it must not touch
    table = 1 + np.arange(b * P_MAX, dtype=np.int32).reshape(b, P_MAX)
    table[6:8] = paged_kv.TRASH_PAGE
    n = 1 + b * P_MAX + 2                 # and two pages nobody names
    kp, kr = jax.random.split(jax.random.PRNGKey(h_kv))
    plane = jax.random.normal(kp, (n, h_kv, PS, D), jnp.float32).astype(dtype)
    rows = jax.random.normal(kr, (b, 1, h_kv, D), jnp.float32).astype(dtype)
    table, pos = jnp.asarray(table), jnp.asarray(pos, jnp.int32)

    want = paged_kv._write_pages(plane, rows, table, pos, None)
    traced = jax.make_jaxpr(paged_kv.paged_write)(plane, rows, table, pos)
    assert 'paged_row_write' in str(traced)
    got = jax.jit(paged_kv.paged_write)(plane, rows, table, pos)

    assert got.dtype == plane.dtype and got.shape == plane.shape
    np.testing.assert_array_equal(_bits(got)[1:], _bits(want)[1:])
    # the rows are where they belong, and nothing else of a live page moved
    for slot, off in enumerate(offsets):
        page = int(table[slot, 0])
        np.testing.assert_array_equal(_bits(got[page, :, off]),
                                      _bits(rows[slot, 0]))
    np.testing.assert_array_equal(_bits(got[int(table[5, 1]), :, 0]),
                                  _bits(rows[5, 0]))
    untouched = [int(p) for p in table[8]] + [n - 2, n - 1]
    np.testing.assert_array_equal(_bits(got)[untouched],
                                  _bits(plane)[untouched])
    changed = np.flatnonzero(
        (_bits(got) != _bits(plane)).reshape(n, -1).any(axis=1))
    assert set(changed) <= {0, 1, 3, 5, 7, 9, 12}, changed


def _plane(h=2, dtype=jnp.bfloat16, ps=PS, d=D):
    return jnp.zeros((5, h, ps, d), dtype)


def _rows(t=1, h=2, dtype=jnp.bfloat16, d=D):
    return jnp.ones((2, t, h, d), dtype)


@pytest.mark.parametrize('case', [
    'a prefill', 'rows past valid', 'an int8 bank', 'a headless plane',
    'half-filled lanes', 'pages of no whole tile', 'no kernel gate'])
def test_everything_but_a_decode_steps_row_takes_the_path_it_took(case):
    """``paged_write`` chooses from what it can see in its input: only ONE
    row a sequence, none of them padding, into a head-major float plane of
    whole lanes and whole sublane tiles, where the platform's kernel gate
    is open, is the row kernel's. The rest is written a page at a time (or
    row by row into a plane without heads), as before."""
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([5, 130], jnp.int32)
    pages, rows, valid = _plane(), _rows(), None
    if case == 'a prefill':
        rows, pos = _rows(t=3), jnp.zeros((2,), jnp.int32)
    elif case == 'rows past valid':
        valid = jnp.asarray([1, 0], jnp.int32)
    elif case == 'an int8 bank':
        pages = jax.tree_util.tree_map(lambda a: a[0],
                                       init_kv_bank((1, 5, 2, PS, D)))
    elif case == 'a headless plane':
        pages, rows = jnp.zeros((5, PS, 640), jnp.bfloat16), jnp.ones(
            (2, 1, 640), jnp.bfloat16)
    elif case == 'half-filled lanes':
        pages, rows = _plane(d=64), _rows(d=64)
    elif case == 'pages of no whole tile':
        pages, pos = _plane(ps=8), jnp.asarray([5, 9], jnp.int32)
    if case != 'no kernel gate':
        fa.set_interpret(True)
    try:
        traced = str(jax.make_jaxpr(
            lambda *a: paged_kv.paged_write(*a, valid))(
                pages, rows, table, pos))
        assert 'paged_row_write' not in traced and 'pallas' not in traced
        # and the row kernel's own call at these sizes, for the contrast
        fa.set_interpret(True)
        assert 'paged_row_write' in str(jax.make_jaxpr(
            paged_kv.paged_write)(_plane(), _rows(), table, pos))
    finally:
        fa.set_interpret(False)


def test_the_row_kernel_under_a_mesh_splits_the_heads_and_no_sequence(
        interpret):
    """Under the engine's mesh the call goes through
    ``mesh_kernel.sharded_call``: heads over 'mp' as the pool lies, every
    sequence's row on every device (a plane is whole along 'dp': a copy
    that took only its share of the rows would differ from its twin)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.ops import mesh_kernel
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ('dp', 'mp'))
    heads = NamedSharding(mesh, P(None, 'mp', None, None))
    plane = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(0), (5, 4, PS, D)), heads)
    rows = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 4, D))
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([5, 130], jnp.int32)
    want = paged_kv._write_pages(plane, rows, table, pos, None)
    got = mesh_kernel.jit(paged_kv.paged_write, mesh)(plane, rows, table, pos)
    assert 'shard_map' in str(jax.make_jaxpr(
        mesh_kernel.jit(paged_kv.paged_write, mesh))(plane, rows, table, pos))
    assert got.sharding.is_equivalent_to(heads, 4)
    np.testing.assert_array_equal(np.asarray(got)[1:], np.asarray(want)[1:])
