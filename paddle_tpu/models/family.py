"""What the serving engine needs of a model family, and nothing else.

``serving.GenerationEngine`` schedules slots and pages; what a page holds
and how a forward pass reads it is the family's. A family is the pair
(pool maker, cached forward) with what placement and telemetry need beside
it, registered for its config class:

    init_pool(config, num_pages, page_size)
        -> {plane name: array or int8 bank}; every plane's axis 1 is pages
           (``ops/paged_kv.copy_page`` copies a page across all of them) and
           page 0 is the trash page
    forward_with_cache(params, tokens, cache, pos, config, last_only=False,
                       partitioner=None) -> (logits, cache)
        ``cache`` is the pool's planes beside ``page_table`` [B, P_max],
        ``valid`` [B] (prefill) and the static ``tail`` flag; the cache that
        comes back holds the planes updated, and may hold ``counts``: a
        small int32 array that rides in the step's one host read and is
        handed to ``note_counts(counts, phase)``

The engine never asks what kind of model it serves: it looks the family up
by the config's class (``family_of``).
"""
import dataclasses
import typing

from ..ops.paged_kv import POOL_LOGICAL_AXES


@dataclasses.dataclass(frozen=True)
class GenerationFamily:
    name: str
    init_pool: typing.Callable
    forward_with_cache: typing.Callable
    # params' logical axes for a mesh (parallel/mesh_engine.py); None: the
    # family has no rules table and serves on one chip
    logical_axes: typing.Any = None
    # one pool plane's logical axes; a bank's scale plane drops the last
    pool_logical_axes: tuple = POOL_LOGICAL_AXES
    # float params -> the int8 weight-only snapshot (precision='int8_wo')
    quantize_decode_params: typing.Optional[typing.Callable] = None
    # (counts: np.ndarray, phase: 'prefill' | 'decode') -> None
    note_counts: typing.Optional[typing.Callable] = None
    # a prefill may start past row 0 and read the rows before it out of the
    # pool (what a prefix cache's hit needs); False: the engine refuses the
    # family a prefix cache
    tail_prefill: bool = True


_FAMILIES = {}


def register(config_cls, family):
    _FAMILIES[config_cls] = family
    return family


def family_of(config):
    """The family registered for ``config``'s class (or a base of it)."""
    for cls in type(config).__mro__:
        if cls in _FAMILIES:
            return _FAMILIES[cls]
    raise TypeError(
        f'no generation family is registered for {type(config).__name__}: '
        f'models/family.register(<config class>, GenerationFamily(...))')
