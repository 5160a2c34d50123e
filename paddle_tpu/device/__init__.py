"""Device/place management. Reference: python/paddle/device/__init__.py.

TPU-native: places map to JAX devices; ``set_device`` pins the default JAX
device. ``TPUPlace`` is first-class (the reference's CUDAPlace analogue).
"""
import jax


class _Place:
    kind = 'cpu'

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f'{type(self).__name__}({self.device_id})'

    def jax_device(self):
        """The JAX device this place names. A place whose kind the process
        does not have raises — it never resolves to another kind."""
        devs = (jax.devices('cpu') if self.kind == 'cpu' else
                [d for d in jax.devices() if _kind_of(d) == self.kind])
        if not devs:
            raise RuntimeError(
                f'{self!r}: no {self.kind} device in this process (jax '
                f'sees {[d.platform for d in jax.devices()]})')
        return devs[self.device_id % len(devs)]


def _kind_of(dev):
    p = dev.platform.lower()
    if p == 'tpu':
        return 'tpu'
    if p in ('gpu', 'cuda', 'rocm'):
        return 'gpu'
    return 'cpu'


class CPUPlace(_Place):
    kind = 'cpu'


class TPUPlace(_Place):
    kind = 'tpu'


class CUDAPlace(_Place):
    kind = 'gpu'


class NPUPlace(_Place):
    kind = 'npu'


class XPUPlace(_Place):
    kind = 'xpu'


class CUDAPinnedPlace(_Place):
    kind = 'cpu'


_current = None


def set_device(device):
    """set_device('tpu') / 'tpu:0' / 'cpu'. Raises when the process has no
    device of that kind."""
    global _current
    if isinstance(device, _Place):
        place = device
    else:
        name, _, idx = str(device).partition(':')
        idx = int(idx) if idx else 0
        place = {'cpu': CPUPlace, 'tpu': TPUPlace, 'gpu': CUDAPlace,
                 'xpu': XPUPlace, 'npu': NPUPlace}.get(name, TPUPlace)(idx)
    jax.config.update('jax_default_device', place.jax_device())
    _current = place
    return place


def get_device():
    if _current is not None:
        return f'{_current.kind}:{_current.device_id}'
    d = jax.devices()[0]
    return f'{_kind_of(d)}:{d.id}'


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_tpu():
    return any(_kind_of(d) == 'tpu' for d in jax.devices())


def device_count():
    return len(jax.devices())


def is_compiled_with_rocm():
    return False


def get_cudnn_version():
    """No cuDNN in the TPU stack (reference returns None when unavailable)."""
    return None


from . import cuda  # noqa: E402,F401


def __getattr__(name):
    # ParallelEnv lives in distributed; resolve lazily to keep the top-level
    # import light (distributed is a lazy subpackage)
    if name == 'ParallelEnv':
        from ..distributed import ParallelEnv
        return ParallelEnv
    raise AttributeError(f'module {__name__!r} has no attribute {name!r}')
