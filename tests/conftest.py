"""Test config: 8 virtual CPU devices so distributed tests run anywhere."""
import os

os.environ.setdefault('XLA_FLAGS',
                      '--xla_force_host_platform_device_count=8')
os.environ['JAX_PLATFORM_NAME'] = 'cpu'
os.environ['JAX_PLATFORMS'] = 'cpu'

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

import pytest  # noqa: E402

# The on-disk compile cache stays off in the test process unless a test
# turns it on (tests/test_warmup.py): engines and hapi.Model enable it on
# construction, and entries left in <repo>/.jax_cache by an earlier run
# would turn this run's compiles into cache hits, which tests count.
from paddle_tpu import warmup  # noqa: E402

warmup.disable_persistent_cache()


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu
    paddle_tpu.seed(42)
    yield


@pytest.fixture(scope='session')
def cpu_mesh():
    """Mesh builder over the 8 virtual CPU devices.

    Returns ``make(dp=, mp=, pp=, sharding=, sp=, ep=)`` building (and
    installing as the process topology) a HybridTopology with those degrees.
    Session-scoped: meshes are cached by degree tuple so repeated tests
    share device layouts instead of re-deriving them.
    """
    from paddle_tpu.distributed import topology as topo_mod
    cache = {}

    def make(dp=1, mp=1, pp=1, sharding=1, sp=1, ep=1):
        key = (dp, mp, pp, sharding, sp, ep)
        if key not in cache:
            cache[key] = topo_mod.HybridTopology(
                dp=dp, mp=mp, pp=pp, sharding=sharding, sp=sp, ep=ep)
        topo_mod.set_topology(cache[key])
        return cache[key]

    prev = topo_mod._current
    yield make
    if prev is not None:
        topo_mod.set_topology(prev)


@pytest.fixture
def read_first():
    """-> a context manager under which every ``GenerationEngine`` reads a
    decode step before it plans the next one: the order the engine had
    before its loop ran one step ahead of its read-back (PR 36). A
    test-local patch of ``_plan_step``, which gives no step a successor
    while its tokens are unread; so every row's input token is the host's
    and ``steps_overlapped`` stays 0. What the pipelined loop serves is
    held equal to what this one serves."""
    import contextlib

    from paddle_tpu.serving import GenerationEngine

    @contextlib.contextmanager
    def patched():
        plan = GenerationEngine._plan_step
        GenerationEngine._plan_step = (
            lambda self, unread: None if unread is not None
            else plan(self, unread))
        try:
            yield
        finally:
            GenerationEngine._plan_step = plan
    return patched


@pytest.fixture(scope='session')
def full_body():
    """-> a context manager under which every ``GenerationEngine`` built
    pads every prompt to ``prefill_width``: the one body an engine had
    before it chose among ``family.prefill_widths``. A test-local patch of
    the rule; what the narrow bodies serve is held equal to what this one
    serves."""
    import contextlib

    from paddle_tpu.models import family

    @contextlib.contextmanager
    def patched():
        rule = family.prefill_widths
        family.prefill_widths = (
            lambda width, page_size, pages=1: (int(width),))
        try:
            yield
        finally:
            family.prefill_widths = rule
    return patched


@pytest.fixture
def traces_for():
    """-> ``count(widths, rows)``: the traces an engine of these
    ``prefill_widths`` has made once it has served prompts of these
    (uncached) rows with no ``warmup()`` before them: its step, and its
    prefill at each width one of them was padded to."""
    def count(widths, rows):
        return 1 + len({next(w for w in widths if w >= n) for n in rows})
    return count
