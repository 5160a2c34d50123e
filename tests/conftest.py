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
    """-> ``family_contract.reading_first``: a context manager under which
    every ``GenerationEngine`` reads a decode step before it plans the next
    one. What the pipelined loop serves is held equal to what this one
    serves."""
    import family_contract
    return family_contract.reading_first


def pytest_generate_tests(metafunc):
    """``refusal``: one case for each shape the bound row of
    tests/served_families.py says its family refuses."""
    row = getattr(metafunc.cls, 'row', None)
    if row is not None and 'refusal' in metafunc.fixturenames:
        metafunc.parametrize('refusal', row.refused, ids=[
            '_'.join(over) for over, _ in row.refused])
