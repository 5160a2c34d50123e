"""The table of served families: one row for each family registered in
``models/family._FAMILIES``, holding what a test needs to hold the family
to the engine's contract (tests/family_contract.py) and to its plain
reference: the tiny float32 configuration, the weights (the reference's,
and the same as the program stacks them), the reference forward, the
engine's arguments and the prompt lengths, the tolerance, the kernel-sized
shape for the Pallas interpreter, and the module constants a tiny shape
needs cut. What the family object says itself (``tail_prefill``,
``page_kinds``, ``prefill_pages``, ``serve_params``) is read from it, not
restated here.

A helper, not collected. A ``model_config`` PR adds ONE row here, a
three-line binding of ``family_contract.Contract`` in its own test file,
and the tests of its own layer; tests/test_served_families.py fails when a
family is registered without a row, or a row is bound by no file."""
import contextlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import (afmoe, brumby, family, gpt, granite_hybrid,
                               latent_moe, moe_gpt, zaya)
from paddle_tpu.ops import retention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference(name):
    """benchmark/reference/<name>.py by path: plain jnp, imports nothing of
    the program. Read, never edited."""
    path = os.path.join(REPO, 'benchmark', 'reference', name + '.py')
    spec = importlib.util.spec_from_file_location('ref_' + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prompts_of(lens, vocab=96, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).astype(np.int32) for n in lens]


def float32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _fields(cls, shape):
    return {k: v for k, v in shape.items() if k in cls.__dataclass_fields__}


class Row:
    """One served family. The defaults are what most rows share: seven
    requests on three slots over pages of 4 rows, prompts of 1, 2 and 3
    rows (a tail that reaches before row 0) among ones that cross pages,
    chunks, windows and the prefill's widths."""
    name = None
    module = None           # paddle_tpu.models.<family>
    config_cls = None
    reference_name = None   # benchmark/reference/<this>.py; None: its own
    cell = None             # benchmark/configs/<this>.json; None: no cell
    vocab = 96
    engine = dict(num_slots=3, page_size=4, prefill_width=40)
    prompts = (5, 21, 33, 12, 1, 2, 3)
    tol = 2e-5              # served float32 rows against the reference
    forward_tol = 5e-6      # the uncached forward against the reference
    # one step ahead against reading first, where a slot changes hands
    # with a step in flight (0: to the last bit)
    step_ahead_tol = 0.0
    # a pool too small for three growing sequences: what ``num_pages`` is
    # cut to (None: the family holds no page, and nothing is evicted)
    short_pool = 11
    # module constants cut to the tiny shape: (module, name, value)
    patches = ()
    # {config field: value} a config is refused for -> the message's words
    refused = ()
    # the names of the leaves ``serve_params`` holds in the compute type,
    # but under the subtrees that keep a type of their own
    matrices = ()
    kept_float32 = ()
    # route()'s keywords of a family whose routed layer is
    # ``parallel/routed_experts.routed_experts``; None: it calls no such
    routed = None

    def __init__(self):
        self.ref = (load_reference(self.reference_name)
                    if self.reference_name else None)

    # -- the configuration ---------------------------------------------------
    def shape(self, **over):
        raise NotImplementedError

    def kernel(self):
        """-> (shape, engine keywords, prompt lengths, new tokens (one
        count, or one a request), tol, patches): heads, pages and widths
        of whole lanes, what the Pallas kernels take, for a run through the
        interpreter."""
        raise NotImplementedError

    def config(self, shape, **over):
        own = _fields(self.config_cls, shape)
        own.update(dtype='float32', param_dtype='float32')
        own.update(over)
        return self.config_cls(**own)

    # -- weights and the two forwards ----------------------------------------
    def weights(self, shape, seed=3, edit=None):
        """-> (the reference's float32 weights, the same as the family
        scans them); ``edit(a layer's leaves)`` changes what the PROGRAM
        gets, the reference keeping its own."""
        raise NotImplementedError

    def reference(self, layers, tokens, shape):
        """[B, T] tokens -> [B, T, V] float32 logits, the plain way."""
        return self.ref.forward(layers, tokens, shape)

    def forward(self, stacked, tokens, config):
        """The program's uncached forward."""
        return self.module.forward(stacked, tokens, config)

    # -- what a run is made under --------------------------------------------
    @contextlib.contextmanager
    def patched(self, patches=None):
        patches = self.patches if patches is None else patches
        before = [(mod, name, getattr(mod, name)) for mod, name, _ in patches]
        for mod, name, value in patches:
            setattr(mod, name, value)
        try:
            yield
        finally:
            for mod, name, value in before:
                setattr(mod, name, value)

    @property
    def family(self):
        return family.family_of(self.config(self.shape()))


# ---- gpt, moe_gpt: held to their own uncached forward ----------------------

class Gpt(Row):
    """Two query heads a KV head: a cached block makes q, k, v by the
    product and then the split (``gpt._cached_qkv``), the uncached forward
    by ``_block_qkv`` as training does."""
    name, module, config_cls = 'gpt', gpt, gpt.GPTConfig
    cell = 'gpt-1.3b-serve'
    forward_tol = 0.0       # the reference IS the uncached forward
    matrices = gpt.PRODUCT_OPERANDS
    extra = {}

    def shape(self, **over):
        shape = dict(vocab_size=96, hidden_size=64, num_layers=2,
                     num_heads=4, num_kv_heads=2, max_seq_len=64,
                     remat=False, use_flash=False, **self.extra)
        shape.update(over)
        return shape

    def kernel(self):
        shape = self.shape(hidden_size=128, num_heads=2, num_kv_heads=1,
                           max_seq_len=512, use_flash=True)
        return (shape, dict(num_slots=2, page_size=128, prefill_width=256),
                (200, 140, 100), 5, 1e-4, ())

    def weights(self, shape, seed=3, edit=None):
        params = self.module.init_params(self.config(shape),
                                         jax.random.PRNGKey(seed))
        if edit is None:
            return params, params
        return params, dict(params, blocks=edit(dict(params['blocks'])))

    def reference(self, layers, tokens, shape):
        out = self.module.forward(layers, tokens, self.config(shape))
        return out[0] if isinstance(out, tuple) else out    # moe: (rows, aux)

    def forward(self, stacked, tokens, config):
        out = self.module.forward(stacked, tokens, config)
        return out[0] if isinstance(out, tuple) else out

    refused = ((dict(num_kv_heads=3), 'num_kv_heads'),)


class MoeGpt(Gpt):
    """Capacity for every row whatever the routing: a body's rows compete
    for expert capacity, which follows the rows of the call."""
    name, module, config_cls = 'moe_gpt', moe_gpt, moe_gpt.MoEConfig
    cell = None
    matrices = moe_gpt.PRODUCT_OPERANDS
    extra = dict(n_experts=4, capacity_factor=8.0)
    # a routed layer groups a step's rows: a row's last bits follow its
    # neighbours, which differ between the two orders
    step_ahead_tol = 2e-5


# ---- the five with a plain reference under benchmark/reference/ ------------

class _Listed(Row):
    """A family whose program takes the reference's own tree: a list of
    layers."""

    def weights(self, shape, seed=3, edit=None):
        layers = float32(self.ref.init_params(shape,
                                              jax.random.PRNGKey(seed)))
        if edit is None:
            return layers, layers
        return layers, dict(layers, layers=[edit(dict(lp))
                                            for lp in layers['layers']])


class LatentMoe(_Listed):
    name, module = 'latent_moe', latent_moe
    config_cls = latent_moe.LatentMoEConfig
    reference_name, cell = 'dots_vlm', 'dots-vlm1-ep16-serve'
    vocab = 64
    forward_tol = 2e-5
    step_ahead_tol = 2e-5       # routed: as moe_gpt
    routed = dict(top_k='num_experts_per_tok', n_group='n_group',
                  topk_group='topk_group', scale='routed_scaling_factor',
                  normalise='norm_topk_prob')
    YARN = dict(type='yarn', factor=40, original_max_position_embeddings=16,
                beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)

    def shape(self, **over):
        shape = dict(
            vocab_size=64, hidden_size=128, intermediate_size=256,
            moe_intermediate_size=128, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=128, qk_nope_head_dim=8, qk_rope_head_dim=8,
            v_head_dim=8, n_routed_experts=4, router_width=16, held_first=4,
            n_shared_experts=1, num_experts_per_tok=4, n_group=4,
            topk_group=2, routed_scaling_factor=2.5, norm_topk_prob=True,
            rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=self.YARN,
            max_position_embeddings=256)
        shape.update(over)
        return shape

    def kernel(self):
        # the tiny shape is the kernels' already: a latent row of 128 + 8
        # values in two lanes, over pages of 128 rows. Three requests on
        # two slots: the third is admitted, into the slot the second left
        # after 5 tokens, while the first is half way through its 12
        return (self.shape(), dict(num_slots=2, page_size=128, num_pages=5,
                                   prefill_width=32), (20, 9, 13),
                (12, 5, 6), 2e-5, ())

    def config(self, shape, **over):
        own = _fields(self.config_cls, shape)
        own.update(n_routed_experts=shape['router_width'],
                   held=(shape['held_first'], shape['n_routed_experts']),
                   dtype='float32', param_dtype='float32')
        own.update(over)
        return self.config_cls(**own)

    refused = ((dict(held_first=14), 'outside'),)


SLIDING, FULL = 'sliding_attention', 'full_attention'


class Afmoe(_Listed):
    """A window of 8 rows over pages of 4: prompts from inside one window
    to four windows deep."""
    name, module, config_cls = 'afmoe', afmoe, afmoe.AfmoeConfig
    reference_name, cell = 'trinity_large', 'trinity-large-ep8-serve'
    forward_tol = 2e-5
    step_ahead_tol = 2e-5       # routed: as moe_gpt
    short_pool = {'full': 25, 'window': 7}
    routed = dict(top_k='num_experts_per_tok', scale='route_scale',
                  normalise='route_norm')

    def shape(self, **over):
        shape = dict(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=5,
            num_dense_layers=1, num_attention_heads=6,
            num_key_value_heads=1, head_dim=8, sliding_window=8,
            layer_types=[SLIDING] * 4 + [FULL], num_experts=4,
            num_experts_per_tok=2, num_shared_experts=1, route_scale=2.448,
            route_norm=True, rms_norm_eps=1e-5, rope_theta=10000,
            mup_enabled=True, max_position_embeddings=64, held_first=0,
            router_width=8)
        shape.update(over)
        return shape

    def kernel(self):
        # window 200 over pages of 128, heads of 64, a request past the
        # window among them
        shape = self.shape(head_dim=64, sliding_window=200,
                           num_hidden_layers=2, layer_types=[SLIDING, FULL],
                           max_position_embeddings=512)
        return (shape, dict(num_slots=2, page_size=128, prefill_width=384),
                (300, 140, 380), 6, 5e-5, ())

    def config(self, shape, **over):
        own = _fields(self.config_cls, shape)
        own.update(num_experts=shape['router_width'],
                   held=(shape['held_first'], shape['num_experts']),
                   dtype='float32', param_dtype='float32')
        own.update(over)
        return self.config_cls(**own)

    refused = ((dict(held_first=6), 'outside'),)


M, A = granite_hybrid.MAMBA, granite_hybrid.ATTENTION


class GraniteHybrid(Row):
    """Two periods of [mamba, mamba, attention, mamba] at hidden 64, the
    scan's chunk cut to 8 rows (the shape's own ``mamba_chunk_size``) so
    that the tiny prompts cross chunks."""
    name, module = 'granite_hybrid', granite_hybrid
    config_cls = granite_hybrid.GraniteHybridConfig
    reference_name, cell = 'granite_hybrid', 'granite-4.0-h-micro-serve'
    forward_tol = 2e-6
    matrices = granite_hybrid.MATRICES

    def shape(self, **over):
        shape = dict(
            vocab_size=96, hidden_size=64, shared_intermediate_size=96,
            num_hidden_layers=8, layer_types=[M, M, A, M] * 2,
            num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
            mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4,
            mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=8,
            attention_multiplier=0.0625, embedding_multiplier=12.0,
            residual_multiplier=0.22, logits_scaling=8.0, rms_norm_eps=1e-5,
            max_position_embeddings=64)
        shape.update(over)
        return shape

    def kernel(self):
        # heads of 64 over pages of 128 rows, two KV heads a pool row;
        # three bodies: 128, 256 and 384 rows
        shape = self.shape(hidden_size=128, num_attention_heads=2,
                           num_key_value_heads=2, mamba_d_head=64,
                           max_position_embeddings=512)
        return (shape, dict(num_slots=2, page_size=128, prefill_width=384),
                (300, 140, 380, 100), 6, 5e-5, ())

    def weights(self, shape, seed=3, edit=None):
        layers = float32(self.ref.init_params(shape,
                                              jax.random.PRNGKey(seed)))
        edit = edit or (lambda lp: lp)
        return layers, {
            'embed': layers['embed'], 'norm_f': layers['norm_f'],
            'periods': granite_hybrid.stack_periods(
                self.config(shape),
                lambda l: edit(dict(layers['layers'][l])))}

    refused = (
        (dict(layer_types=[M, 'window'] * 4), 'layer_types'),
        (dict(num_key_value_heads=3), 'num_key_value_heads'),
        (dict(mamba_n_groups=2), 'one group'),
        (dict(mamba_n_heads=3), 'mamba_expand'),
        (dict(hidden_size=48, mamba_n_heads=3, num_attention_heads=2,
              num_key_value_heads=2), '128'))


class Zaya(Row):
    name, module, config_cls = 'zaya', zaya, zaya.ZayaConfig
    reference_name, cell = 'zaya', 'zaya1-8b-pp2-serve'
    # to rounding: which rows share a step's expert tiles differs between
    # the two orders
    step_ahead_tol = 1e-6
    matrices = zaya.MATRICES
    kept_float32 = ('router',)      # its matrices follow ``router_dtype``

    def shape(self, **over):
        shape = dict(
            vocab_size=96, hidden_size=64, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, cca_time0=2, cca_time1=2,
            num_experts=4, num_experts_per_tok=1, router_hidden_size=32,
            partial_rotary_factor=0.5, rope_theta=5000000.0,
            rms_norm_eps=1e-5, max_position_embeddings=64)
        shape.update(over)
        return shape

    def kernel(self):
        # heads of 128 over pages of 128 rows, widths of whole lanes; two
        # prefill widths
        shape = self.shape(hidden_size=128, moe_intermediate_size=128,
                           num_hidden_layers=2, num_attention_heads=2,
                           head_dim=128, max_position_embeddings=512)
        return (shape, dict(num_slots=2, page_size=128, prefill_width=256),
                (200, 140, 100), 5, 1e-4, ())

    def weights(self, shape, seed=3, edit=None):
        layers = float32(self.ref.init_params(shape,
                                              jax.random.PRNGKey(seed)))
        edit = edit or (lambda lp: lp)
        return layers, {
            'embed': layers['embed'], 'norm_f': layers['norm_f'],
            'layers': zaya.stack_layers(
                self.config(shape),
                lambda l: edit(dict(layers['layers'][l])))}

    refused = (
        (dict(held=(8, 9)), 'outside'),
        (dict(num_attention_heads=3), 'must divide'),
        (dict(cca_time1=3), 'what is written'),
        (dict(num_experts_per_tok=2), 'what is written'),
        (dict(num_key_value_heads=4), 'what is written'))


class Brumby(Row):
    """No page at all: ``page_size`` is the granule of the prefill's widths
    and nothing else. A prefill's chunk is cut to the tiny prompts' size
    (``ops/retention.CHUNK`` 8), so that they cross chunks as the real
    ones cross chunks of 128: prompts of one row, of a whole chunk and of
    two among others."""
    name, module, config_cls = 'brumby', brumby, brumby.BrumbyConfig
    reference_name, cell = 'brumby', 'brumby-14b-pp5-serve'
    engine = dict(num_slots=3, page_size=8, prefill_width=40)
    prompts = (5, 21, 33, 12, 1, 8, 16)
    step_ahead_tol = 1e-6
    short_pool = None
    patches = ((retention, 'CHUNK', 8),)
    matrices = brumby.MATRICES

    def shape(self, **over):
        shape = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                     num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
                     rope_theta=1000000.0, max_position_embeddings=64)
        shape.update(over)
        return shape

    def kernel(self):
        # a head of 128 (D = 8,256 features in 65 lane tiles), two query
        # heads a KV head
        shape = self.shape(hidden_size=128, num_attention_heads=2,
                           num_key_value_heads=1, head_dim=128,
                           max_position_embeddings=128)
        return (shape, dict(num_slots=2, page_size=16, prefill_width=64),
                (40, 9), 4, 1e-4, ((retention, 'CHUNK', 16),))

    def weights(self, shape, seed=3, edit=None):
        layers = float32(self.ref.init_params(shape,
                                              jax.random.PRNGKey(seed)))
        edit = edit or (lambda lp: lp)
        return layers, dict(
            {k: layers[k] for k in ('embed', 'head', 'norm_f')},
            layers=family.stack_layers(
                shape['num_hidden_layers'],
                lambda l: edit(dict(layers['layers'][l]))))

    refused = ((dict(num_key_value_heads=3), 'must divide'),
               (dict(head_dim=15), 'even'))


FAMILIES = {row.name: row for row in (
    Gpt(), MoeGpt(), LatentMoe(), Afmoe(), GraniteHybrid(), Zaya(),
    Brumby())}
