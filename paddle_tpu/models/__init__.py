"""Flagship model families (training-scale, TPU-first functional cores)."""
from . import gpt  # noqa: F401
from . import ernie  # noqa: F401
from . import moe_gpt  # noqa: F401
from . import latent_moe  # noqa: F401
from . import afmoe  # noqa: F401
from . import granite_hybrid  # noqa: F401
from . import zaya  # noqa: F401
from . import brumby  # noqa: F401
from .decode_cache import DecodeFnCache, clear_decode_caches  # noqa: F401
from .crnn import CRNN  # noqa: F401
from .ppyolo_lite import PPYOLOE, PPYOLOELite  # noqa: F401
from .svtr import SVTRLite  # noqa: F401
