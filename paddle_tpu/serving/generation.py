"""GenerationEngine: continuous (iteration-level) batching for LLM decode.

``InferenceEngine`` batches whole requests; autoregressive generation
can't wait for a batch — requests arrive ragged, produce different
numbers of tokens, and a fixed-batch ``generate()`` call head-of-line
blocks every sequence on the longest one. This engine schedules at the
*iteration* level (the Orca discipline, PAPERS.md arxiv 2309.06180 /
2604.15464): a fixed number of decode **slots** runs ONE compiled decode
step per iteration, and the host scheduler admits new sequences into free
slots and retires finished ones *between* steps. Two jitted functions
serve the whole workload:

 - ``prefill``: batch-1, a prompt padded to the narrowest of
   ``prefill_widths`` that holds it (``models/family.prefill_widths``:
   the powers of two and their midpoints from an eighth of
   ``prefill_width`` up), ONE jitted function and one executable a width,
   all built by ``warmup()``; pad rows are routed to the paged pool's
   trash page and the last REAL row's logits sample token 0;
 - ``step``: all ``num_slots`` rows advance one token — inactive slots
   decode garbage into the trash page and their sample is discarded.

The decode loop runs ONE STEP AHEAD of its read-back: step N+1 is
dispatched before step N's tokens are read, its input tokens taken where
step N left them on the device, so the host's work a step (the step's
arrays, the dispatch, the read, emission, admission) runs while the device
computes and the device's queue is never empty between two steps. The next
step's only true dependence on a read is its input token; positions, page
tables, seeds and an end by count or by the context are known to the host
before a step runs. An end by ``eos_id`` is learnt one step late and costs
the one row that step computed for the slot, which is dropped. What is in
flight when, and what ``slot.pos`` means at each point, is in
``_decode_step``'s docstring.

What a page holds is the model family's (``models/family.py``): the
engine asks the family of the config for its pool (a dict of planes whose
axis 1 is pages: K and V a head for ``gpt``, one latent row for
``latent_moe``) and hands it back whole to the family's cached forward.
State lives in that paged pool (``ops/paged_kv.py``): fixed-size pages
in one shared buffer, a per-slot page table, and a host-side free-list
allocator, so slot occupancy — not worst-case sequence length — bounds
HBM. A family with several KINDS of plane (``family.page_kinds``: window
layers beside full ones) gets an allocator and a table a kind; a window
kind's slot holds only the pages its next step reads, and every page
that has left the window goes back to the free list before that step. A
kind that is a row a SLOT (``per_slot``: the recurrent state of layers
that keep no rows) has no allocator and no table: its planes are made with
one row a slot, a call is told which slots its sequences are, a prefill
overwrites its slot's row, and ``stats()`` counts the busy slots' rows as
state held beside the pages in use. A family of per-slot kinds ALONE (no
layer keeps a row) is served with no page pool at all: no allocator, no
table, ``num_pages`` 0; admission needs a free slot and nothing else, and
a sequence's length is bounded by ``max_seq_len`` positions.
Pages are allocated lazily at each page boundary; on exhaustion the
most-recently-admitted active slot — possibly the requester itself — is
evicted (pages freed, request requeued at the queue FRONT), so the oldest
sequence always advances and no pair of growing sequences can livelock
each other. Sampling keys are derived per slot as
``fold_in(PRNGKey(seed), position)``, so a restarted sequence
regenerates byte-identical tokens and its future never re-emits ones
already streamed.

Robustness / telemetry reuse the serving stack: bounded admission queue
(``QueueFullError``), per-request deadlines (``DeadlineExceededError``),
a ``fault.CircuitBreaker`` + ``gen.step`` chaos point around device
calls, ``gen.*`` metrics in the observability registry, and warmup
manifest capture (a ``gen_prefill`` entry a width and the ``gen_decode``
entry) so a new process prebuilds every executable before traffic.

With ``prefix_cache=True`` the engine indexes finished sequences' pages
in a :class:`~.prefix_cache.PrefixCache` (tenant-namespaced trie over
page-aligned chunks): a later request with a cached prefix is admitted
with those pages pre-mapped and prefills only the uncached tail through
the SAME prefill function, at the width its TAIL needs (the tail start
position is a traced argument — zero new traces once that width has run
or ``warmup()`` built it, provable via ``_trace_count``); an exact
``(prompt, seed)`` repeat skips the prefill device call entirely and
replays the recorded first token (near-zero TTFT). Shared pages are
refcounted by the allocator; mid-page divergence copies the page
(copy-on-write) before any write, and cache residency is released LRU
before any live slot is ever evicted for pages.

Env knobs: ``PADDLE_TPU_GEN_SLOTS`` (default 8),
``PADDLE_TPU_GEN_PAGE_SIZE`` (default 128, clamped to max_seq_len),
``PADDLE_TPU_GEN_PREFIX`` (=1 enables the prefix cache by default).
"""
import functools as _functools
import itertools
import os
import sys
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from .. import fault
from .. import observability as _obs
from ..models import family as _family
from ..models import gpt as _gpt
from ..ops import paged_attention as _pa
from ..ops import paged_kv as _pkv
from .errors import DeadlineExceededError, EngineClosedError, QueueFullError
from .prefix_cache import PrefixCache

ENV_SLOTS = 'PADDLE_TPU_GEN_SLOTS'
ENV_PAGE_SIZE = 'PADDLE_TPU_GEN_PAGE_SIZE'
ENV_PREFIX = 'PADDLE_TPU_GEN_PREFIX'

_HIST_WINDOW = 4096

# sentinel distinguishing "deadline not supplied" from "no deadline": the
# fleet router must be able to resubmit a deadline-free request as such
_UNSET = object()


def _env_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class GenerationFuture:
    """Handle for one submitted sequence. ``result()`` blocks for the full
    token list; ``stream()`` yields tokens as decode iterations emit them.
    Eviction/readmission never re-yields: regenerated tokens are only
    appended past what the future already holds."""

    def __init__(self, want_logits=False):
        self._cv = threading.Condition()
        self._tokens = []
        # a token's (logits row as computed, the family's note on the row)
        self._logits = [] if want_logits else None
        self._done = False
        self._exc = None
        self._listeners = []

    # ---- engine-internal ------------------------------------------------
    def _count(self):
        with self._cv:
            return len(self._tokens)

    def _snapshot(self, n):
        """First ``n`` emitted tokens (the prefix-cache publisher's view of
        what the KV rows past the prompt hold)."""
        with self._cv:
            return [int(t) for t in self._tokens[:n]]

    def subscribe(self, fn):
        """Register ``fn(kind, *args)`` invoked OUTSIDE the future's lock:
        ``('token', idx, tok)`` per emission and ``('finish', exc)`` once.
        Tokens already emitted are replayed so a late subscriber (a fleet
        router attaching to a resubmitted request) misses nothing. Callers
        must tolerate out-of-order delivery across the replay/live seam —
        the index identifies each token's position."""
        with self._cv:
            self._listeners.append(fn)
            replay = list(enumerate(self._tokens))
            done, exc = self._done, self._exc
        for i, t in replay:
            fn('token', i, t)
        if done:
            fn('finish', exc)

    def _append(self, tok, logits=None):
        with self._cv:
            if self._done:
                return
            self._tokens.append(int(tok))
            if self._logits is not None:
                self._logits.append(logits)
            idx = len(self._tokens) - 1
            listeners = list(self._listeners)
            self._cv.notify_all()
        # listeners run outside the lock: they may touch other futures /
        # router queues whose locks must never nest inside this one
        for fn in listeners:
            fn('token', idx, int(tok))

    def _finish(self, exc=None):
        with self._cv:
            if self._done:
                return False
            self._done = True
            self._exc = exc
            listeners = list(self._listeners)
            self._cv.notify_all()
        for fn in listeners:
            fn('finish', exc)
        return True

    # ---- caller API -----------------------------------------------------
    def done(self):
        with self._cv:
            return self._done

    def exception(self, timeout=None):
        with self._cv:
            if not self._cv.wait_for(lambda: self._done, timeout):
                raise TimeoutError('generation still running')
            return self._exc

    def result(self, timeout=None):
        exc = self.exception(timeout)
        if exc is not None:
            raise exc
        with self._cv:
            return list(self._tokens)

    def logits(self):
        """One float32 ``[vocab]`` row per token emitted so far, aligned
        with the tokens: the logits each token was chosen from, as the
        engine's executable computed them (before temperature and top-k).
        Only for a request submitted with ``want_logits=True``. The engine
        keeps a row in the type its executable computed it in; it is
        widened here, once, on the caller's thread and not the
        scheduler's."""
        with self._cv:
            if self._logits is None:
                raise ValueError('this request did not ask for logits: '
                                 'submit(..., want_logits=True)')
            for i, (row, note) in enumerate(self._logits):
                if row.dtype != np.float32:
                    self._logits[i] = (row.astype(np.float32), note)
            return [row for row, _ in self._logits]

    def row_notes(self):
        """What the family said of each emitted token's row beside its
        logits (``forward_with_cache``'s 'row_notes': one float32 vector a
        row), aligned with the tokens; None a row for a family that says
        nothing. Only for a request submitted with ``want_logits=True``."""
        with self._cv:
            if self._logits is None:
                raise ValueError('this request did not ask for logits: '
                                 'submit(..., want_logits=True)')
            return [note for _, note in self._logits]

    def stream(self, timeout=None):
        """Generator of tokens in emission order; returns at EOS/limit,
        raises the failure exception if the sequence failed."""
        i = 0
        while True:
            with self._cv:
                if not self._cv.wait_for(
                        lambda: self._done or i < len(self._tokens), timeout):
                    raise TimeoutError('generation stalled')
                if i < len(self._tokens):
                    tok = self._tokens[i]
                    i += 1
                elif self._exc is not None:
                    raise self._exc
                else:
                    return
            yield tok


class _Request:
    __slots__ = ('prompt', 'eff_max_new', 'seed', 'future', 'enqueue_t',
                 'deadline_t', 'evictions', 'ttft_noted', 'rec', 'tenant',
                 'want_logits')

    def __init__(self, prompt, eff_max_new, seed, future, enqueue_t,
                 deadline_t, rec=None, tenant='default', want_logits=False):
        self.prompt = prompt
        self.want_logits = want_logits
        self.eff_max_new = eff_max_new
        self.seed = seed
        self.tenant = tenant
        self.future = future
        self.enqueue_t = enqueue_t
        self.deadline_t = deadline_t
        self.evictions = 0
        self.ttft_noted = False
        # request-scoped trace record (observability.reqtrace); the shared
        # no-op singleton when the layer is disabled
        self.rec = rec if rec is not None else _obs.NULL_RECORD


class _Slot:
    __slots__ = ('req', 'pos', 'ahead', 'last_tok', 'produced', 'table',
                 'tables', 'first', 'admit_seq', 'start', 'cow', 'first_tok')

    def __init__(self, req, tables, first, admit_seq, start=0, cow=None,
                 first_tok=None):
        self.req = req
        # the KV row the next step to be DISPATCHED writes: it advances
        # when a step is handed to the device, not when its token is read
        self.pos = len(req.prompt)
        self.ahead = 0                  # steps dispatched and still unread:
                                        # rows below pos - ahead are read
        self.last_tok = 0               # the last token the host has read
        self.produced = 0               # tokens read and emitted
        self.tables = tables            # kind -> np [p_max] i32, 0 = none
        # the first kind's (what a prefix cache publishes); None with no
        # paged kind
        self.table = next(iter(tables.values()), None)
        self.first = first              # kind -> first page still held
        self.admit_seq = admit_seq
        self.start = start              # first prompt row prefill computes
                                        # (cached rows < start are mapped)
        self.cow = cow                  # pending (src, dst) page copy
        self.first_tok = first_tok      # full prefix hit: replay this token
                                        # instead of running prefill


class _Step:
    """One decode step handed to the device and not read yet: which slot
    each row was computed for (None: no sequence's row, it went to the
    trash page) and the step's results, still on the device."""
    __slots__ = ('rows', 'want', 'rids', 'overlapped', 'inputs', 'toks',
                 'out', 'lg')

    def __init__(self, rows, want, rids, overlapped, inputs):
        self.rows = rows                # slot index -> _Slot or None
        self.want = want                # a row's request asked for logits
        self.rids = rids
        self.overlapped = overlapped    # dispatched with its forerunner
                                        # still unread
        self.inputs = inputs            # host-built, until it is dispatched
        self.toks = None                # [slots] sampled tokens: the next
                                        # step's input, never read
        self.out = None                 # the tokens with a family's counts
                                        # behind them: the one host read
        self.lg = None                  # ``_kept_rows``: [slots, vocab] logits


ASK_ROWS = 8    # logits rows a gather hands the host at a time


@jax.jit
def _take_rows(lg, idx):
    """Rows ``idx`` of a step's logits ``[slots, vocab]`` (and of a
    family's notes on them, where ``lg`` is the pair), on the device: a
    step's rows are read by the host only for the slots whose request asked
    (``want_logits``), and a row is the whole vocabulary wide."""
    return jax.tree_util.tree_map(lambda a: jnp.take(a, idx, axis=0), lg)


def _kept_rows(logits, cache):
    """What a call leaves on the device for the rows whose request asked:
    the logits, and beside them what the family notes of each row
    (``cache['row_notes']`` [rows, n] float32) where it notes anything."""
    notes = cache.get('row_notes')
    return logits if notes is None else (logits, notes)


def _host_rows(kept):
    """``_kept_rows``' result read -> (logits [rows, vocab] in the type
    they were computed in, notes [rows, n] or None)."""
    if isinstance(kept, tuple):
        return np.asarray(kept[0]), np.asarray(kept[1])
    return np.asarray(kept), None


def _with_counts(tokens, counts):
    """The sampled tokens with a family's counts (int32) behind them, so
    that both come to the host in the step's one read."""
    if counts is None:
        return tokens
    return jnp.concatenate([tokens, counts.astype(tokens.dtype)])


def _resolve_generation_model(net, config, forward_fn):
    """Accept a GPTForCausalLM-style Layer (has .config + _params) or a
    (params, config) functional pair. -> (params, config, the config's
    model family, the cached forward: the family's unless one is given)."""
    if config is None:
        cfg = getattr(net, 'config', None)
        if cfg is None:
            raise TypeError(
                'GenerationEngine needs a model with a .config or an '
                'explicit (params, config) pair')
        if hasattr(net, '_decode_params'):
            params = net._decode_params()
        else:
            params = net._params()
    else:
        params, cfg = net, config
    family = _family.family_of(cfg)
    params = jax.tree_util.tree_map(jnp.asarray, params)
    return params, cfg, family, forward_fn or family.forward_with_cache


class GenerationEngine:
    """Continuous-batching generation over one causal-LM model.

    ``submit(prompt)`` returns a ``GenerationFuture`` immediately; the
    scheduler thread prefills it into a free slot and advances it one
    token per decode iteration alongside every other active sequence.
    Sampling knobs (temperature/top_k/top_p, greedy by default) are
    engine-wide — one executable — while the RNG seed is per-request.

    Between two iterations of the scheduler at most one decode step is in
    flight: dispatched, its tokens not yet read (``_inflight``). A slot's
    ``pos`` is the row the next step to be DISPATCHED writes and
    ``produced`` the tokens read and emitted; they differ by ``ahead``,
    the slot's steps in flight, and everything that publishes, evicts or
    finishes goes by what was read (``pos - ahead``). ``stats()`` counts
    ``steps_overlapped`` (steps dispatched while their forerunner was
    unread) beside ``steps``, and ``rows_discarded`` (rows computed for a
    slot that had ended by EOS or been evicted before they were read; such
    a row is no token and reaches no future), and, for a family with
    pages, ``paged_steps_walked`` beside ``paged_steps_dense``: the grid
    steps a full layer's paged decode kernel walks for the steps planned
    (the pages the slots hold, one for an idle slot) and the steps of a
    grid over every slot's whole table.

    ``precision=None`` (or ``'float32'``: "not the int8 snapshot") holds
    the family's product operands (``family.serve_params``: for ``gpt`` the
    four stacked block matrices) in the configuration's compute dtype and
    everything else as given, so a bfloat16-compute engine over float32
    parameters rounds each matrix once, at construction, to the bits that
    every call used to round it to; where the two dtypes agree it holds
    exactly the leaves it was given. ``'int8_wo'`` holds the family's int8
    snapshot of the parameters as given. ``stats()['param_bytes']`` says
    what is held, by dtype.
    """

    _seq = itertools.count()

    def __init__(self, net, config=None, *, num_slots=None, page_size=None,
                 num_pages=None, prefill_width=None, temperature=0.0,
                 top_k=None, top_p=None, eos_id=None, queue_capacity=64,
                 default_deadline_ms=None, breaker=None, autostart=True,
                 forward_fn=None, clock=None, precision=None,
                 telemetry_port=None, prefix_cache=None,
                 prefix_cache_pages=None, mesh=None, mp=None):
        from .. import warmup as _warmup_mod
        _warmup_mod.ensure_persistent_cache()
        if precision not in (None, 'float32', 'int8_wo'):
            raise ValueError(
                f"GenerationEngine precision must be None/'float32'/"
                f"'int8_wo', got {precision!r}")
        params, cfg, family, fwd = _resolve_generation_model(
            net, config, forward_fn)
        self._family = family
        if precision == 'int8_wo':
            from ..ops.weight_only import is_weight_only
            if family.quantize_decode_params is None:
                raise ValueError(
                    f"the {family.name} family has no int8_wo snapshot")
            if not any(is_weight_only(v) for v in params.values()):
                # the family's snapshot (int8 matrices, per-output-channel
                # scales); a model already snapshot (e.g. via
                # enable_int8_decode) passes through untouched
                params = family.quantize_decode_params(params)
        # after the snapshot, which quantizes the parameters as given, and
        # before placement: the family's product operands in the compute
        # dtype, cast here once and not in every call. The given leaves are
        # not kept: what a caller drops is freed
        if family.serve_params is not None:
            params = family.serve_params(params, cfg)
        # mesh-sharded replica (mp=N): ONE SPMD program over N chips.
        # Params are placed by the logical-axis rules table, the forward
        # pins the KV pool to the kv_heads layout, and everything else —
        # scheduler, allocator, page tables, trace count — is the mp=1
        # code verbatim (parallel/mesh_engine.py).
        from ..parallel import mesh_engine as _mesh
        self._mesh_ctx = _mesh.resolve(mesh, mp=mp)
        if self._mesh_ctx is not None:
            if precision == 'int8_wo':
                raise ValueError(
                    'mesh-sharded engines do not support precision='
                    "'int8_wo' yet: the quantized bank pytree has no "
                    'logical-axis annotations to place')
            params = self._mesh_ctx.place_params(params, cfg)
            fwd = _functools.partial(
                fwd, partitioner=self._mesh_ctx.partitioner)
        self._params = params
        # what the engine holds, by dtype: read once, here (``stats()``)
        self._param_bytes = {}
        for leaf in jax.tree_util.tree_leaves(params):
            self._param_bytes[leaf.dtype.name] = (
                self._param_bytes.get(leaf.dtype.name, 0) + int(leaf.nbytes))
        self.config = cfg
        self._forward_fn = fwd
        self._precision = precision or 'float32'

        s_max = int(cfg.max_seq_len)
        self.max_seq_len = s_max
        self.num_slots = int(num_slots if num_slots is not None
                             else _env_int(ENV_SLOTS, 8))
        ps = int(page_size if page_size is not None
                 else min(_env_int(ENV_PAGE_SIZE, 128), s_max))
        if ps < 1:
            raise ValueError(f'page_size must be >= 1, got {ps}')
        self.page_size = ps
        self.p_max = _pkv.pages_for(s_max, ps)
        self.prefill_width = int(prefill_width if prefill_width is not None
                                 else s_max)
        if not 1 <= self.prefill_width <= s_max:
            raise ValueError(
                f'prefill_width {self.prefill_width} outside '
                f'[1, {s_max}]')
        # what a prompt is padded to: the narrowest of these that holds it
        self.prefill_widths = _family.prefill_widths(
            self.prefill_width, ps, family.prefill_pages)
        # the family's kinds of plane; one ('kv') unless it names them.
        # A slot holds at most ``_held_max[kind]`` pages of a kind: the
        # table's width, or what a window spans. A kind that is a row a
        # slot (recurrent state) has no pages: it is kept apart, and
        # everything below that allocates, tables or releases walks the
        # paged kinds alone. A family may name per-slot kinds ONLY: the
        # engine then holds no page, no allocator and no table, admits by
        # free slots, and ``page_size`` is the granule of ``prefill_widths``
        # and nothing else
        kinds = (family.page_kinds(cfg) if family.page_kinds
                 else _family.ONE_KIND)
        self._kinds = tuple(k for k in kinds if not k.per_slot)
        self._slot_kinds = tuple(k for k in kinds if k.per_slot)
        self._held_max = {
            k.name: (self.p_max if k.window is None else min(
                self.p_max, _pa.window_pages(k.window, ps)))
            for k in self._kinds}
        # +1: page 0 is the reserved trash page
        if not isinstance(num_pages, dict):
            num_pages = {k.name: num_pages for k in self._kinds}
        self._num_pages = {
            name: int(n if n is not None
                      else self.num_slots * self._held_max[name] + 1)
            for name, n in num_pages.items()}
        if set(self._num_pages) != set(self._held_max):
            raise ValueError(
                f'num_pages names {sorted(self._num_pages)}, the '
                f'{family.name} family\'s kinds are '
                f'{sorted(self._held_max)}')
        self.num_pages = sum(self._num_pages.values())
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.queue_capacity = int(queue_capacity)
        self.default_deadline_ms = default_deadline_ms
        self._breaker = breaker if breaker is not None else \
            fault.CircuitBreaker(failure_threshold=5, recovery_timeout=5.0)
        self._clock = clock or time.monotonic
        self._autostart = autostart

        self._pool = self._init_pool()
        # what a step with no forerunner in flight is handed in place of
        # the previous step's tokens (every row of such a step takes the
        # host's token), placed as a step leaves its own: under a mesh
        # replicated over it, so that both kinds of call are one executable
        self._no_prev = jnp.zeros((self.num_slots,), jnp.int32)
        if self._mesh_ctx is not None:
            self._no_prev = jax.device_put(self._no_prev,
                                           self._mesh_ctx.replicated())
        self._unit_bytes = self._kind_unit_bytes(kinds)
        self._state_bytes_per_slot = sum(
            self._unit_bytes[k.name] for k in self._slot_kinds)
        self._allocs = {name: _pkv.PageAllocator(n)
                        for name, n in self._num_pages.items()}
        # the first paged kind's (what a prefix cache indexes): None for a
        # family of per-slot state alone, which is refused one below
        self._alloc = next(iter(self._allocs.values()), None)
        # prefix cache: opt-in (constructor flag, giving it a residency
        # bound, or the env knob) — page accounting changes when finished
        # sequences stay resident, so it is never silently enabled
        if prefix_cache is None:
            prefix_cache = (prefix_cache_pages is not None
                            or _env_int(ENV_PREFIX, 0) > 0)
        if prefix_cache and (not family.tail_prefill
                             or family.page_kinds is not None):
            raise ValueError(
                f'the {family.name} family prefills from row 0 only: it '
                f'cannot read a cached prefix, so no prefix cache')
        self._prefix = (PrefixCache(self._alloc, ps, prefix_cache_pages)
                        if prefix_cache else None)
        self._slots = [None] * self.num_slots
        self._queue = deque()
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._thread = None
        self._closed = False
        self._draining = False
        self._admit_seq = 0
        self._inflight = None       # the decode step dispatched and unread
        self._trace_count = 0
        self._fns = None
        # kind -> AOT Compiled executable, seeded by warmup/prebuild; the
        # live path prefers these (a jit callable's first real call would
        # still pay the executable build even when the trace is cached)
        self._aot = {}
        self._start_t = self._clock()
        self._n = {k: 0 for k in ('submitted', 'completed', 'rejected',
                                  'expired', 'failed', 'evictions',
                                  'tokens', 'prefills',
                                  'prefill_rows_asked',
                                  'prefill_rows_computed', 'steps',
                                  'steps_overlapped', 'rows_discarded',
                                  'paged_steps_walked', 'paged_steps_dense',
                                  'prefix_hits', 'prefix_misses',
                                  'prefix_full_hits', 'prefix_tokens_saved',
                                  'prefix_evictions')}
        self._make_metrics()
        # readiness + optional telemetry plane (same contract as
        # InferenceEngine: /readyz = warm AND breaker closed AND queue
        # below capacity; telemetry_port=0 picks a free port)
        self._warmed = False
        self._probe_name = f'serving.{self.labels["engine"]}'
        _obs.add_readiness(self._probe_name, self._readiness_probe)
        self.telemetry = (_obs.serve_telemetry(port=telemetry_port)
                          if telemetry_port is not None else _obs.NULL_SERVER)

    def _init_pool(self):
        """Fresh page pool, as the model family makes it: a dict of planes
        whose axis 1 is pages, opaque to the engine. Under a mesh it is
        placed by the family's pool axes (the allocator and page tables
        stay host-side either way)."""
        units = dict(self._num_pages,
                     **{k.name: self.num_slots for k in self._slot_kinds})
        pool = self._family.init_pool(
            self.config, (units if self._family.page_kinds
                          else self.num_pages), self.page_size)
        if self._mesh_ctx is not None:
            pool = self._mesh_ctx.place_pool(
                pool, self._family.pool_logical_axes)
        return pool

    def _kind_unit_bytes(self, kinds):
        """{kind: bytes of one page of it (of one slot's row of a per-slot
        kind)}, from the pool's planes as the family made them: the planes
        a kind names, or for the one kind that names none every plane that
        no other names."""
        named = {p for k in kinds for p in k.planes}
        out = {}
        for k in kinds:
            planes = k.planes or [n for n in self._pool if n not in named]
            out[k.name] = sum(
                int(leaf.nbytes) // int(leaf.shape[1]) for n in planes
                for leaf in jax.tree_util.tree_leaves(self._pool[n]))
        return out

    def _held_bytes_locked(self):
        """(bytes of per-slot state the busy slots hold, bytes of the
        pages in use): a slot's state is held from admission to its end,
        whatever its length."""
        active = sum(1 for s in self._slots if s is not None)
        state = active * self._state_bytes_per_slot
        pages = sum(a.used_pages * self._unit_bytes[name]
                    for name, a in self._allocs.items())
        return state, pages

    def _readiness_probe(self):
        with self._lock:
            depth = len(self._queue)
            closed = self._closed
        warm = (self._warmed or self._fns is not None
                or all(k in self._aot for k in self._aot_names()))
        breaker = self._breaker.state
        ready = (warm and breaker == 'closed'
                 and depth < self.queue_capacity and not closed)
        return {'ready': ready, 'warm': warm, 'breaker': breaker,
                'queue_depth': depth, 'queue_capacity': self.queue_capacity,
                'closed': closed}

    # ---- telemetry -------------------------------------------------------
    def _make_metrics(self):
        # UNIFORMITY: the label set is identical at every mesh degree —
        # fleet/host/SLO lookups key on exactly {'engine': ...}, and the
        # registry matches label sets exactly, so adding a mesh label here
        # would silently detach every control-plane rule from an mp>1
        # replica. The mesh degree is published as its own gauge series
        # (gen.mesh_devices, labelled engine+mesh) for /metrics slicing.
        labels = self.labels = {'engine': f'g{next(GenerationEngine._seq)}'}
        if self._mesh_ctx is not None and _obs.enabled():
            _obs.registry().gauge(
                'gen.mesh_devices',
                {**self.labels, 'mesh': f'mp{self._mesh_ctx.mp}'}
            ).set(self._mesh_ctx.size)
        if _obs.enabled():
            reg = _obs.registry()
            mk_c = lambda n, **k: reg.counter(n, {**labels, **k})  # noqa: E731
            mk_h = lambda n: reg.histogram(n, labels,           # noqa: E731
                                           window=_HIST_WINDOW)
            mk_g = lambda n, **k: reg.gauge(n, {**labels, **k})  # noqa: E731
        else:
            mk_c = lambda n, **k: _obs.Counter(n, {**labels, **k})  # noqa: E731
            mk_h = lambda n: _obs.Histogram(n, labels,          # noqa: E731
                                            window=_HIST_WINDOW)
            mk_g = lambda n, **k: _obs.Gauge(n, {**labels, **k})  # noqa: E731
        self._c = {k: mk_c(f'gen.requests_{k}') for k in
                   ('submitted', 'completed', 'rejected', 'expired',
                    'failed')}
        self._c['evictions'] = mk_c('gen.evictions')
        self._c['tokens'] = mk_c('gen.tokens')
        # a prefill's rows: what the prompts asked (their uncached tails)
        # and what the bodies they were padded to computed
        for rows in ('asked', 'computed'):
            self._c[f'prefill_rows_{rows}'] = mk_c('gen.prefill_rows_total',
                                                   rows=rows)
        # the decode loop one step ahead of its read-back: steps dispatched
        # while their forerunner was unread (beside stats()['steps']), and
        # rows computed for a slot that had ended or been evicted meanwhile
        self._c['steps_overlapped'] = mk_c('gen.steps_overlapped')
        self._c['rows_discarded'] = mk_c('gen.rows_discarded')
        # gen.prefix.*: the prefix-cache surface fleetobs federates
        self._c['prefix_hits'] = mk_c('gen.prefix.hits')
        self._c['prefix_misses'] = mk_c('gen.prefix.misses')
        self._c['prefix_full_hits'] = mk_c('gen.prefix.full_hits')
        self._c['prefix_tokens_saved'] = mk_c('gen.prefix.tokens_saved')
        self._c['prefix_evictions'] = mk_c('gen.prefix.evictions')
        self._h = {'prefill': mk_h('gen.prefill_ms'),
                   'step': mk_h('gen.decode_step_ms'),
                   'ttft': mk_h('gen.ttft_ms'),
                   # same series the batch engines emit, labelled gN — the
                   # fleet autoscaler's per-replica p99 rules key on it.
                   # Observed at admit from the ORIGINAL enqueue_t, which
                   # requeue-after-eviction preserves: a preempted request's
                   # wait is never under-reported.
                   'queue_wait': mk_h('serve.queue_wait_ms')}
        self._g = {'occupancy': mk_g('gen.slot_occupancy'),
                   'pages': mk_g('gen.page_utilization'),
                   'prefix_pages': mk_g('gen.prefix.cached_pages')}
        # the pool by kind of plane: pages the slots hold now, and pages
        # given back because they left a window (none of a kind without)
        self._g_kind = {k.name: mk_g('kv.pages_in_use', kind=k.name)
                        for k in self._kinds}
        self._c_released = {
            k.name: mk_c('kv.pages_released_total', kind=k.name)
            for k in self._kinds if k.window is not None}
        # a family with per-slot state: what the busy slots hold of it, in
        # bytes, beside the bytes of the pages in use
        self._g_bytes = ({'state': mk_g('kv.state_bytes_held'),
                          'pages': mk_g('kv.page_bytes_held')}
                         if self._slot_kinds else None)

    def _note(self, key, n=1):
        self._n[key] += n
        c = self._c.get(key)
        if c is not None:
            c.inc(n)

    def _update_gauges_locked(self):
        active = sum(1 for s in self._slots if s is not None)
        self._g['occupancy'].set(active / max(self.num_slots, 1))
        # page 0 (the reserved trash page) is excluded from the
        # denominator: a fully loaded pool reads 1.0
        used = {name: a.used_pages for name, a in self._allocs.items()}
        usable = max(self.num_pages - len(used), 1)
        self._g['pages'].set(sum(used.values()) / usable)
        for name, n in used.items():
            self._g_kind[name].set(n)
        if self._g_bytes is not None:
            state, pages = self._held_bytes_locked()
            self._g_bytes['state'].set(state)
            self._g_bytes['pages'].set(pages)
        if self._prefix is not None:
            self._g['prefix_pages'].set(self._prefix.cached_pages)
            ev = self._prefix.stats()['evictions']
            delta = ev - self._n['prefix_evictions']
            if delta > 0:
                self._note('prefix_evictions', delta)

    # ---- compiled fns ----------------------------------------------------
    def _build_fns(self):
        cfg, fwd = self.config, self._forward_fn
        temperature, top_k, top_p = self.temperature, self.top_k, self.top_p

        def sample_rows(lg, seeds, positions):
            if temperature == 0:
                # greedy: per-row argmax, batch-composition independent
                return jnp.argmax(lg, axis=-1).astype(jnp.int32)

            def one(row, seed, p):
                # the key depends only on (seed, input position): a
                # restarted/evicted sequence regenerates identical tokens
                key = jax.random.fold_in(jax.random.PRNGKey(seed), p)
                return _gpt._sample(row[None], temperature, top_k, top_p,
                                    key=key)[0]
            return jax.vmap(one)(lg, seeds, positions)

        def prefill(params, pool, prompt, start, valid, page_table, seed):
            self._trace_count += 1      # trace-time side effect
            # 'tail': True (a STATIC pytree key — the dict never crosses a
            # jit boundary) routes T>1 attention through the paged kernel
            # so rows past ``start`` attend prefix pages written by an
            # earlier sequence. ONE executable a width serves cold
            # prefills (start=0) and cached-prefix tails alike: start is
            # traced, so prefix-cache hits never trace or compile anything
            # new.
            cache = dict(pool, page_table=page_table, valid=valid,
                         tail=True)
            pos0 = start.astype(jnp.int32)
            logits, cache = fwd(params, prompt, cache, pos0, cfg,
                                last_only=True)
            # absolute position start+valid-1: the sampling key of the
            # prompt's last row must not depend on how much was cached
            row = logits[:, 0]
            tok = sample_rows(row, seed, pos0 + valid.astype(jnp.int32) - 1)
            # the logits the token was chosen from stay on the device in
            # the compute dtype; the host reads them, and widens them to
            # float32 when it is asked for them (``logits()``), only for a
            # request that asked (want_logits)
            return (_with_counts(tok, cache.get('counts')),
                    _kept_rows(row, cache), {k: cache[k] for k in pool})

        def step(params, pool, prev, tok, fresh, pos, page_table, seeds):
            self._trace_count += 1
            # a row's input token is the one the previous step sampled for
            # it, taken where that step left it on the device (``prev``:
            # its fourth result, which the host need not have read), unless
            # the host says otherwise (``fresh``): a slot just prefilled,
            # and every row of a step with no forerunner in flight. Data,
            # not a second trace: one executable serves every step
            tok = jnp.where(fresh, tok, prev)
            cache = dict(pool, page_table=page_table)
            logits, cache = fwd(params, tok[:, None], cache, pos, cfg)
            rows = logits[:, 0]
            nxt = sample_rows(rows, seeds, pos)
            if self._mesh_ctx is not None:
                # it comes back in as ``prev``: placed as ``_no_prev`` is
                nxt = jax.lax.with_sharding_constraint(
                    nxt, self._mesh_ctx.replicated())
            return (_with_counts(nxt, cache.get('counts')),
                    _kept_rows(rows, cache), {k: cache[k] for k in pool},
                    nxt)

        # under a mesh the paged kernel shards over it (ops/mesh_kernel)
        from ..ops import mesh_kernel
        mesh = self._mesh_ctx.mesh if self._mesh_ctx else None
        return (mesh_kernel.jit(prefill, mesh, donate_argnums=(1,)),
                mesh_kernel.jit(step, mesh, donate_argnums=(1,)))

    def _tables(self, rows, of=None, slots=None):
        """The page-table argument of a compiled call for ``rows`` slots:
        one [rows, p_max] int32 array, or {kind: one} for a family that
        names its kinds. ``of(kind name) -> [rows, p_max]`` fills it;
        without it the tables are empty (what warmup lowers). A per-slot
        kind's entry is ``slots``: [rows] int32, which slots the call's
        sequences are."""
        of = of or (lambda name: np.zeros((rows, self.p_max), np.int32))
        if self._family.page_kinds is None:
            return of(self._kinds[0].name)
        tables = {k.name: of(k.name) for k in self._kinds}
        if slots is None:
            slots = np.zeros((rows,), np.int32)
        tables.update({k.name: slots for k in self._slot_kinds})
        return tables

    def _fns_pair(self):
        if self._fns is None:
            self._fns = self._build_fns()
        return self._fns

    def _aot_names(self):
        """``_aot``'s keys once warm: the step and a prefill a width."""
        return ['gen_decode'] + [f'gen_prefill.{w}'
                                 for w in self.prefill_widths]

    def _manifest_entries(self):
        from ..warmup.manifest import generation_entry
        geom = dict(slots=self.num_slots, page_size=self.page_size,
                    num_pages=self.num_pages,
                    prefill_width=self.prefill_width,
                    table_width=self.p_max)
        return [generation_entry('gen_prefill', body=w, **geom)
                for w in self.prefill_widths] + [
                    generation_entry('gen_decode', **geom)]

    def _maybe_record(self):
        wm = sys.modules.get('paddle_tpu.warmup.manifest')
        if wm is not None and wm.capturing():
            for e in self._manifest_entries():
                wm.record(e)

    def warmup(self):
        """AOT-compile the decode step and the prefill at every one of
        ``prefill_widths`` before traffic (zero cold-start: a live call
        after this neither retraces nor recompiles, whatever the prompt's
        length). Returns the prebuild report dict."""
        from .. import warmup as _warmup_mod
        man = _warmup_mod.Manifest()
        for e in self._manifest_entries():
            man.add(e)
        report = _warmup_mod.prebuild(man, generation=self)
        # and the gather that hands the host the asking slots' logits rows
        if 'gen_decode' in self._aot:
            kept = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype),
                self._aot['gen_decode'].out_info[1])
            _take_rows(kept, jnp.zeros((ASK_ROWS,), jnp.int32))
        if self._prefix is not None:
            # pre-compile the COW copy executable too — a trash-page
            # self-copy is a no-op on real data, and without it the first
            # mid-page cache hit would pay the compile in its TTFT
            with self._lock:
                self._pool = _pkv.copy_page(self._pool, 0, 0)
        self._warmed = True          # flips the /readyz warm check
        return report

    # ---- lifecycle -------------------------------------------------------
    def start(self):
        with self._lock:
            if self._closed:
                raise EngineClosedError('engine already shut down')
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._scheduler_loop,
                    name='paddle-tpu-generation-sched', daemon=True)
                self._thread.start()
        return self

    def shutdown(self, drain=True, timeout=None):
        """Stop the scheduler. ``drain=True`` finishes every admitted and
        queued sequence first; otherwise their futures fail with
        EngineClosedError."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self._draining = drain
            failed = []
            if not drain:
                failed = [r for r in self._queue]
                self._queue.clear()
                for i, slot in enumerate(self._slots):
                    if slot is not None:
                        failed.append(slot.req)
                        self._free_slot_locked(i)
            inline = drain and self._thread is None
            self._cv.notify_all()
        for r in failed:
            err = EngineClosedError('engine shut down')
            r.rec.note('cancel')
            r.rec.finish('cancelled', err)
            if r.future._finish(err):
                self._note('failed')
        if inline:
            self._drain_inline()
        if self._thread is not None:
            self._thread.join(timeout)
        if self._prefix is not None:
            with self._lock:
                self._prefix.clear()
        _obs.remove_readiness(self._probe_name)
        self.telemetry.stop()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown()
        return False

    # ---- admission -------------------------------------------------------
    def submit(self, prompt, max_new_tokens=32, deadline_ms=None, seed=0,
               tenant='default', *, want_logits=False, _record=None,
               _enqueue_t=None, _deadline_t=_UNSET):
        """Enqueue one sequence. ``prompt`` is a 1-D token id sequence of
        length 1..prefill_width; returns a ``GenerationFuture``. Tokens
        stop at ``eos_id`` (emitted), ``max_new_tokens``, or the context
        window (a prompt of exactly max_seq_len still yields one token).
        ``tenant`` namespaces the prefix cache: KV pages are only ever
        reused within one tenant's own traffic. ``want_logits`` keeps the
        float32 logits every token was chosen from on the future
        (``future.logits()``); the tokens are the same either way.

        The underscore params are the fleet router's resubmission hooks:
        a failed-over request keeps its original ``RequestRecord``,
        submit-time enqueue timestamp, and absolute deadline so queue-wait
        SLO accounting and deadline enforcement stay truthful across
        replicas (timestamps must come from this engine's clock domain —
        ``time.monotonic`` unless a test injected one)."""
        arr = np.asarray(prompt, dtype=np.int32).reshape(-1)
        t0 = int(arr.size)
        if not 1 <= t0 <= self.prefill_width:
            raise ValueError(
                f'prompt length {t0} outside [1, {self.prefill_width}] '
                f'(prefill_width)')
        if int(max_new_tokens) < 1:
            raise ValueError('max_new_tokens must be >= 1')
        # the final decode write lands at position max_seq_len-1; the +1 is
        # the token sampled from that full-window step (same rule as
        # GPTForCausalLM.generate's n_cached)
        eff = min(int(max_new_tokens), self.max_seq_len - t0 + 1)
        deadline_ms = (deadline_ms if deadline_ms is not None
                       else self.default_deadline_ms)
        now = self._clock()
        enqueue_t = _enqueue_t if _enqueue_t is not None else now
        if _deadline_t is not _UNSET:
            deadline_t = _deadline_t
        else:
            deadline_t = (now + deadline_ms / 1e3
                          if deadline_ms is not None else None)
        fut = GenerationFuture(want_logits=bool(want_logits))
        # request-scoped trace: minted here, rides the request across the
        # submit -> scheduler thread boundary (NULL_RECORD when disabled)
        if _record is not None:
            rec = _record
        else:
            rec = _obs.start_request('gen', engine=self.labels['engine'],
                                     prompt_len=t0, max_new=eff)
        fut.request_id = rec.rid
        if deadline_t is not None and now >= deadline_t:
            # already unmeetable: fail fast instead of queueing a request
            # the admitter would only expire after it reached a slot
            waited = (now - enqueue_t) * 1e3
            limit = (deadline_t - enqueue_t) * 1e3
            err = DeadlineExceededError(waited, limit)
            self._note('expired')
            rec.note('expire', waited_ms=round(waited, 3), fast_fail=True)
            rec.finish('expired', err)
            raise err
        req = _Request(arr, eff, int(seed) & 0xFFFFFFFF, fut, enqueue_t,
                       deadline_t, rec=rec, tenant=str(tenant),
                       want_logits=bool(want_logits))
        try:
            with self._cv:
                if self._closed:
                    raise EngineClosedError('engine already shut down')
                if len(self._queue) >= self.queue_capacity:
                    self._note('rejected')
                    raise QueueFullError(self.queue_capacity,
                                         len(self._queue))
                rec.note('enqueue', depth=len(self._queue))
                self._queue.append(req)
                self._note('submitted')
                self._cv.notify_all()
        except Exception as e:
            rec.finish('rejected', e)
            raise
        if self._autostart and self._thread is None:
            self.start()
        return fut

    # ---- scheduler -------------------------------------------------------
    def _scheduler_loop(self):
        """The scheduler thread: admit and prefill what fits, then one
        iteration of the decode loop (``_decode_step``), until the engine
        closes. Between two iterations at most ONE decode step is in
        flight (``self._inflight``: dispatched, its tokens unread); it
        counts as work to do, so the loop does not sleep on it and a
        draining shutdown reads it before the thread returns. A shutdown
        that does not drain has failed every future already: the step in
        flight is dropped unread."""
        while True:
            with self._cv:
                while not self._closed and not self._busy_locked():
                    self._cv.wait(0.05)
                if self._closed and not (self._draining
                                         and self._busy_locked()):
                    return
                admitted = self._admit_locked()
            self._iterate(admitted)

    def _drain_inline(self):
        """Finish all admitted+queued work on the caller's thread (used by
        shutdown(drain=True) when no scheduler thread ever started)."""
        while True:
            with self._cv:
                if not self._busy_locked():
                    return
                admitted = self._admit_locked()
            self._iterate(admitted)

    def _busy_locked(self):
        """Work to do: a queued request, a sequence in a slot, or a step
        in flight whose tokens nobody has read."""
        return bool(self._queue or self._inflight is not None
                    or any(s is not None for s in self._slots))

    def _iterate(self, admitted):
        for idx in admitted:
            self._prefill_one(idx)
        self._decode_step()     # returns at once with no step to take or read

    def _admit_locked(self):
        out = []
        while self._queue:
            free_idx = next((i for i, s in enumerate(self._slots)
                             if s is None), None)
            if free_idx is None:
                break
            req = self._queue[0]
            now = self._clock()
            if req.deadline_t is not None and now > req.deadline_t:
                self._queue.popleft()
                waited = (now - req.enqueue_t) * 1e3
                limit = (req.deadline_t - req.enqueue_t) * 1e3
                err = DeadlineExceededError(waited, limit)
                req.rec.note('expire', waited_ms=round(waited, 3))
                req.rec.finish('expired', err)
                if req.future._finish(err):
                    self._note('expired')
                continue
            need = _pkv.pages_for(len(req.prompt), self.page_size)
            # of a window kind the first decode step reads only the pages
            # from ``first`` on: the prefill's rows before them go nowhere
            first = {k.name: self._first_page(k, len(req.prompt))
                     for k in self._kinds}
            short = [name for name, n in self._num_pages.items()
                     if need - first[name] > n - 1]
            if short:
                self._queue.popleft()
                err = ValueError(
                    f'prompt needs {need - first[short[0]]} pages but the '
                    f'pool only has {self._num_pages[short[0]] - 1} '
                    f'allocatable')
                req.rec.finish('error', err)
                req.future._finish(err)
                self._note('failed')
                continue
            # longest cached prefix: matched full pages arrive retained
            # (this slot's references); the COW source page stays owned by
            # the cache and is copied into a private page before any write
            # a request that wants logits never replays a recorded first
            # token: its last prompt row is prefilled again for the logits
            hit = (self._prefix.acquire(req.tenant, req.prompt, req.seed,
                                        replay=not req.want_logits)
                   if self._prefix is not None else None)
            shared = hit['pages'] if hit else []
            cow_src = hit['cow'] if hit else None
            # the COW destination is one of the `need` logical pages and
            # comes out of the fresh allocation (pages[0] below)
            fresh = need - len(shared)
            got = self._alloc_kinds_locked(
                {name: fresh - at for name, at in first.items()})
            if got is None:
                if shared:
                    self._alloc.free(shared)    # undo; re-acquired on retry
                break       # active slots will free pages; retry next round
            self._queue.popleft()
            tables = {k.name: np.zeros((self.p_max,), np.int32)
                      for k in self._kinds}
            cow = None
            for name, pages in got.items():
                table = tables[name]
                # what a prefix cache shared lies before the fresh pages
                # (only a family of one kind has a cache: ``shared`` is
                # empty and ``cow_src`` None for every other)
                table[:len(shared)] = shared
                if cow_src is not None:
                    cow = (cow_src, pages[0])
                    table[len(shared)] = pages[0]
                    pages = pages[1:]
                if pages:
                    table[need - len(pages):need] = pages
            waited_ms = max(0.0, (now - req.enqueue_t) * 1e3)
            self._h['queue_wait'].observe(waited_ms)
            req.rec.note('admit', slot=free_idx, pages=need,
                         waited_ms=round(waited_ms, 3))
            start, first_tok = 0, None
            if hit is not None:
                start = hit['match']
                first_tok = hit['next_tok']
                self._note('prefix_hits')
                self._note('prefix_tokens_saved', start)
                if first_tok is not None:
                    self._note('prefix_full_hits')
                req.rec.note('prefix_hit', tokens=start,
                             full=first_tok is not None)
            elif self._prefix is not None:
                self._note('prefix_misses')
            self._slots[free_idx] = _Slot(req, tables, first,
                                          self._admit_seq, start=start,
                                          cow=cow, first_tok=first_tok)
            self._admit_seq += 1
            out.append(free_idx)
        if out:
            self._update_gauges_locked()
        return out

    def _prefill_one(self, idx):
        slot = self._slots[idx]
        if slot is None:
            return
        req = slot.req
        t0 = len(req.prompt)
        if slot.cow is not None:
            # copy-on-write: duplicate the shared mid-page before this
            # sequence writes into it (compiled once ever — see copy_page)
            src, dst = slot.cow
            slot.cow = None
            self._pool = _pkv.copy_page(self._pool, src, dst)
        if slot.first_tok is not None:
            # full prefix hit: every prompt row is already in mapped pages
            # and the donor recorded the first sampled token for this seed
            # — no device call at all, TTFT is pure admission latency
            tok = slot.first_tok
            req.rec.note('prefill_skip', slot=idx, prompt_len=t0)
            with self._cv:
                if self._slots[idx] is not slot:
                    return
                slot.last_tok = tok
                self._emit_locked(slot, tok)
                if self._slot_finished(slot, tok):
                    self._finish_slot_locked(idx)
                self._update_gauges_locked()
            return
        start = slot.start
        tail = t0 - start               # uncached rows to prefill
        # the narrowest body that holds them: rows past ``valid`` are
        # padding at any width, so the width is the host's to choose
        body = next(w for w in self.prefill_widths if w >= tail)
        prompt = np.zeros((1, body), np.int32)
        prompt[0, :tail] = req.prompt[start:]
        startv = np.asarray([start], np.int32)
        valid = np.asarray([tail], np.int32)
        table = self._tables(1, lambda name: slot.tables[name][None].copy(),
                             slots=np.asarray([idx], np.int32))
        seed = np.asarray([req.seed], np.uint32)
        self._maybe_record()
        pf = self._aot.get(f'gen_prefill.{body}') or self._fns_pair()[0]
        ahead = self._inflight

        def dev():
            fault.inject('gen.step')
            wall0 = time.perf_counter()
            tok, lg, pool = pf(
                self._params, self._pool, jnp.asarray(prompt),
                jnp.asarray(startv), jnp.asarray(valid),
                jax.tree_util.tree_map(jnp.asarray, table),
                jnp.asarray(seed))
            # the prefill is queued behind the decode step in flight: its
            # own time starts where that step ends on the device (a wait,
            # no read), so gen.prefill_ms keeps meaning the prefill alone
            if ahead is not None and not ahead.toks.is_ready():
                ahead.toks.block_until_ready()
                wall0 = time.perf_counter()
            row = None
            if req.want_logits:
                row, note = _host_rows(lg)
                row = (row[0], None if note is None else note[0])
            tok = np.asarray(tok)
            return int(tok[0]), row, pool, tok[1:], wall0

        req.rec.note('prefill', slot=idx, prompt_len=t0, start=start,
                     body=body)
        try:
            with _obs.span('gen.prefill', slot=idx, prompt_len=t0,
                           req_id=req.rec.rid):
                tok, row, pool, counts, wall0 = self._breaker.call(dev)
        except Exception as e:
            self._handle_device_failure(e)
            return
        self._pool = pool
        self._note_counts(counts, 'prefill')
        self._h['prefill'].observe(1e3 * (time.perf_counter() - wall0))
        self._n['prefills'] += 1
        self._note('prefill_rows_asked', tail)
        self._note('prefill_rows_computed', body)
        with self._cv:
            if self._slots[idx] is not slot:    # shut down meanwhile
                return
            slot.last_tok = tok
            self._emit_locked(slot, tok, row)
            if self._slot_finished(slot, tok):
                self._finish_slot_locked(idx)
            self._update_gauges_locked()

    def _note_counts(self, counts, phase):
        """What a family counted inside a step (routed rows, say), to its
        own counters."""
        if len(counts) and self._family.note_counts is not None:
            self._family.note_counts(counts, phase)

    def _decode_step(self):
        """One iteration of the decode loop, which runs one step ahead of
        its read-back: step N+1 is dispatched from what the host knows
        BEFORE step N's tokens are read, so the device never waits for the
        host between two steps.

        On entry ``self._inflight`` is step N (dispatched by the previous
        iteration, unread) or None (the first step, or the loop drained).
        The iteration plans step N+1 under the lock: pages by ``slot.pos``,
        which is the row the step to be dispatched writes and advances
        HERE; positions, tables and seeds from the slots as they are now;
        the input token left on the device (step N's fourth result) for a
        row whose slot step N also served, the host's ``slot.last_tok``
        (a prefill's token) for any other. A slot whose end the host knows
        (its count, the context) gets no row: its last step is in flight
        already. Then, outside the lock, it dispatches step N+1, reads
        step N (tokens with a family's counts in one read; the logits
        rows of the slots whose request asked, gathered on the device),
        and under the lock again emits a token a row, finishes and frees
        slots. A row whose slot is
        no longer the one it was computed for (ended by EOS one step ago,
        evicted, shut down) is dropped: no token, no listener,
        ``rows_discarded``. On return ``self._inflight`` is step N+1, or
        None when no slot had a step to take; ``slot.pos - slot.ahead``
        rows of a slot are read, ``slot.produced`` tokens emitted.

        One ``gen.decode_step`` span a step READ (the iteration that only
        dispatches, with nothing in flight, opens none): dispatch of the
        successor and the wait for this step's tokens."""
        unread = self._inflight
        ahead = self._plan_step(unread)
        if ahead is None and unread is None:
            return
        self._maybe_record()
        st = self._aot.get('gen_decode') or self._fns_pair()[1]
        wall0 = time.perf_counter()

        def dev():
            fault.inject('gen.step')
            # the asking slots' rows are gathered on the device BEFORE the
            # successor is dispatched, so the gather runs right behind the
            # step it reads and its read lies under the successor
            asked = self._asked_rows(unread) if unread is not None else None
            if ahead is not None:
                tok, fresh, pos, table, seeds = ahead.inputs
                ahead.inputs = None
                ahead.out, ahead.lg, self._pool, ahead.toks = st(
                    self._params, self._pool,
                    self._no_prev if unread is None else unread.toks,
                    jnp.asarray(tok), jnp.asarray(fresh), jnp.asarray(pos),
                    jax.tree_util.tree_map(jnp.asarray, table),
                    jnp.asarray(seeds))
            if unread is None:
                return None, None
            # ONE host readback per step for every slot (a family's
            # counts ride behind the tokens in it); the logits follow only
            # for the rows whose request asked for them
            out, rows = np.asarray(unread.out), {}
            for part, at in asked or ():
                lg, notes = _host_rows(part)
                for j, i in enumerate(at):
                    # copies: a view would keep all ASK_ROWS rows alive
                    rows[i] = (lg[j].copy(),
                               None if notes is None else notes[j].copy())
            return out, rows

        try:
            with (_obs.span('gen.decode_step',
                            slots=sum(r is not None for r in unread.rows),
                            req_ids=unread.rids)
                  if unread is not None else _obs.NULL_SPAN):
                nxt, rows = self._breaker.call(dev)
        except Exception as e:
            self._handle_device_failure(e)
            return
        self._inflight = ahead
        if unread is None:
            return
        s = self.num_slots
        self._note_counts(nxt[s:], 'decode')
        self._h['step'].observe(1e3 * (time.perf_counter() - wall0))
        self._n['steps'] += 1
        if unread.overlapped:
            self._note('steps_overlapped')
        with self._cv:
            for i, slot in enumerate(unread.rows):
                if slot is None:
                    continue
                if self._slots[i] is not slot:
                    # the slot ended or was evicted after this row was
                    # dispatched (the index may hold a NEW slot by now)
                    self._note('rows_discarded')
                    continue
                t = int(nxt[i])
                slot.ahead -= 1
                slot.last_tok = t
                slot.req.rec.note_decode(slot.pos - slot.ahead)
                self._emit_locked(
                    slot, t, rows[i] if slot.req.want_logits else None)
                if self._slot_finished(slot, t):
                    self._finish_slot_locked(i)
            self._update_gauges_locked()
            self._cv.notify_all()

    def _asked_rows(self, step):
        """The logits rows of ``step`` that the host is to read, still on
        the device: [(rows [ASK_ROWS, vocab], the slots they are)], None
        where no row's request asked. A row is the whole vocabulary wide
        (a step's rows are 25 MB at 48 slots of 262,272 logits), so what
        comes to the host is the asking slots' rows alone, ``ASK_ROWS`` at
        a time through one small executable (``_take_rows``; a count that
        followed the askers would compile in the middle of traffic)."""
        if not step.want:
            return None
        at = [i for i, slot in enumerate(step.rows)
              if slot is not None and slot.req.want_logits]
        parts = []
        for lo in range(0, len(at), ASK_ROWS):
            some = at[lo:lo + ASK_ROWS]
            idx = np.zeros((ASK_ROWS,), np.int32)
            idx[:len(some)] = some
            parts.append((_take_rows(step.lg, jnp.asarray(idx)), some))
        return parts

    def _plan_step(self, unread):
        """The next decode step from what the host knows, with ``unread``
        (or no step) in flight before it: -> a ``_Step`` with its
        host-built inputs, not dispatched yet, or None when no slot has a
        step to take. Advances ``slot.pos`` and ``slot.ahead``
        of every slot it gives a row."""
        s = self.num_slots
        tok = np.zeros((s,), np.int32)
        fresh = np.ones((s,), np.bool_)
        pos = np.zeros((s,), np.int32)
        tables = {k.name: np.zeros((s, self.p_max), np.int32)
                  for k in self._kinds}
        seeds = np.zeros((s,), np.uint32)
        rows, rids = [None] * s, []
        want = False
        with self._cv:
            self._ensure_pages_locked()
            for i, slot in enumerate(self._slots):
                if slot is None or not self._steps_on(slot):
                    continue
                if unread is not None and unread.rows[i] is slot:
                    fresh[i] = False    # fed back on the device
                else:
                    tok[i] = slot.last_tok
                pos[i] = slot.pos
                for name, table in tables.items():
                    table[i] = slot.tables[name]
                seeds[i] = slot.req.seed
                rows[i] = slot
                slot.pos += 1
                slot.ahead += 1
                want = want or slot.req.want_logits
                if slot.req.rec.rid:
                    rids.append(slot.req.rec.rid)
        if all(r is None for r in rows):
            return None
        if self._kinds:
            # the grid steps a full layer's paged kernel walks for these
            # rows (ops/paged_attention.page_schedule: the pages a slot
            # holds, an idle slot's one), and a grid of every slot's table
            self._n['paged_steps_walked'] += int(np.minimum(
                pos // self.page_size + 1, self.p_max).sum())
            self._n['paged_steps_dense'] += s * self.p_max
        table = self._tables(s, tables.__getitem__,
                             slots=np.arange(s, dtype=np.int32))
        return _Step(rows, want, rids, overlapped=unread is not None,
                     inputs=(tok, fresh, pos, table, seeds))

    def _steps_on(self, slot):
        """Whether ``slot`` takes another decode step after those in
        flight: its count and the context say so before any token is read
        (``eos_id`` alone ends a sequence one step late)."""
        return (slot.produced + slot.ahead < slot.req.eff_max_new
                and slot.pos < self.max_seq_len)

    # ---- slot state (all called under the lock) --------------------------
    def _emit_locked(self, slot, tok, logits=None):
        req = slot.req
        idx = slot.produced
        slot.produced += 1
        self._note('tokens')
        if idx >= req.future._count():
            req.future._append(tok, logits)
            if not req.ttft_noted:
                req.ttft_noted = True
                ttft_ms = 1e3 * (self._clock() - req.enqueue_t)
                self._h['ttft'].observe(ttft_ms)
                req.rec.note('first_emit', ttft_ms=round(ttft_ms, 3))

    def _slot_finished(self, slot, tok):
        if self.eos_id is not None and tok == self.eos_id:
            return True
        if slot.produced >= slot.req.eff_max_new:
            return True
        return slot.pos - slot.ahead >= self.max_seq_len

    def _free_slot_locked(self, idx):
        slot = self._slots[idx]
        for name, table in slot.tables.items():
            pages = [int(p) for p in table if p != _pkv.TRASH_PAGE]
            if pages:
                self._allocs[name].free(pages)
        self._slots[idx] = None

    def _publish_locked(self, slot):
        """Index a retiring/evicted slot's written pages in the prefix
        cache (called BEFORE the slot's own references are freed, so every
        published page is still live when the cache retains it)."""
        if self._prefix is None:
            return
        req = slot.req
        t0 = len(req.prompt)
        # KV row p >= t0 holds the (p - t0)-th generated token; the final
        # sampled token was emitted but never written, and a row that a
        # step in flight is still writing is not published: by what was
        # READ, rows == slot.pos - slot.ahead
        written = slot.pos - slot.ahead
        gen = req.future._snapshot(written - t0)
        tokens = [int(t) for t in req.prompt] + gen
        first = (req.future._snapshot(1) or [None])[0]
        self._prefix.publish(req.tenant, tokens, slot.table, written,
                             prompt_len=t0, seed=req.seed, first_tok=first)

    def _finish_slot_locked(self, idx):
        slot = self._slots[idx]
        self._publish_locked(slot)
        self._free_slot_locked(idx)
        slot.req.rec.note('retire', produced=slot.produced,
                          evictions=slot.req.evictions)
        slot.req.rec.finish('ok')
        if slot.req.future._finish():
            self._note('completed')
        self._cv.notify_all()

    def _ensure_pages_locked(self):
        """Allocate the next page for any slot crossing a page boundary.
        On pool exhaustion, evict the most-recently-admitted active slot —
        INCLUDING the requester itself (self-preemption). The oldest
        active sequence is therefore never a victim: it monotonically
        advances, finishes, and frees its pages, which bounds every other
        sequence's wait (the no-livelock invariant — evicting "the other
        slot" instead lets two growing sequences destroy each other's
        progress forever). An evicted request requeues at the FRONT and
        later regenerates identical tokens from its seeded keys. Before
        anything is allocated, a window kind's slots give back the pages
        that left their windows."""
        for k in self._kinds:
            if k.window is not None:
                self._release_left_locked(k)
        for i, slot in enumerate(self._slots):
            if slot is None or not self._steps_on(slot):
                continue
            li = slot.pos // self.page_size
            if li >= self.p_max:
                continue
            for k in self._kinds:
                if (self._slots[i] is slot
                        and slot.tables[k.name][li] == _pkv.TRASH_PAGE):
                    self._next_page_locked(i, slot, k.name, li)

    def _first_page(self, kind, pos):
        """The first page of ``kind`` that the step at row ``pos`` reads."""
        if kind.window is None:
            return 0
        return max(0, pos - kind.window + 1) // self.page_size

    def _release_left_locked(self, kind):
        """Give back every page of a window kind that the slots' next
        steps no longer read: a slot then holds ``_held_max`` pages of the
        kind at most, and what it gave back is free for the slots that
        cross a page boundary in this same round."""
        name, alloc = kind.name, self._allocs[kind.name]
        released = 0
        for slot in self._slots:
            if slot is None or not self._steps_on(slot):
                continue
            first, table = self._first_page(kind, slot.pos), slot.tables[name]
            if first > slot.first[name]:
                alloc.free([int(p) for p in table[slot.first[name]:first]])
                table[slot.first[name]:first] = _pkv.TRASH_PAGE
                released += first - slot.first[name]
                slot.first[name] = first
        if released:
            self._c_released[name].inc(released)

    def _next_page_locked(self, i, slot, name, li):
        """Slot ``i``'s page ``li`` of kind ``name``, evicting for it as
        ``_ensure_pages_locked`` says; the slot may be gone on return."""
        while True:
            # cold cache residency yields before any live slot does
            pg = self._alloc_with_release_locked(1, name)
            if pg is not None:
                slot.tables[name][li] = pg[0]
                return
            victim = self._pick_victim_locked()
            only = sum(1 for s in self._slots if s is not None) == 1
            if victim == i and only:
                # alone and exhausted: this request's total demand
                # exceeds the whole pool — retrying cannot succeed
                self._free_slot_locked(i)
                if slot.req.future._finish(RuntimeError(
                        f'request needs more KV pages than the pool '
                        f'holds ({self._num_pages[name] - 1} '
                        f'allocatable)')):
                    self._note('failed')
                return
            self._evict_locked(victim)
            if victim == i:
                return      # self-preempted; re-admitted when pages free

    def _alloc_with_release_locked(self, n, kind=None):
        """``alloc(n)`` of ``kind`` (the first, unnamed), releasing LRU
        prefix-cache residency on failure until the allocation fits or the
        cache is dry (only a family of one kind has a cache). A released
        page only reaches the free list at refcount zero, so keep
        releasing while the cache still holds anything."""
        alloc = self._allocs[kind] if kind else self._alloc
        pages = alloc.alloc(n)
        while pages is None and self._prefix is not None:
            if not self._prefix.release_lru(n):
                break
            pages = alloc.alloc(n)
        return pages

    def _alloc_kinds_locked(self, want):
        """{kind: n pages} for every kind or for none: -> {kind: pages} or
        None, with nothing held."""
        got = {}
        for name, n in want.items():
            got[name] = self._alloc_with_release_locked(n, name)
            if got[name] is None:
                for done, pages in got.items():
                    self._allocs[done].free(pages or ())
                return None
        return got

    def _pick_victim_locked(self):
        best, best_seq = None, -1
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            if slot.admit_seq > best_seq:
                best, best_seq = i, slot.admit_seq
        return best

    def _evict_locked(self, idx):
        slot = self._slots[idx]
        req = slot.req
        # publish what the victim already computed: its re-admission (and
        # anyone sharing its prefix) prefills only past the cached rows
        self._publish_locked(slot)
        self._free_slot_locked(idx)
        req.evictions += 1
        req.rec.note('evict', count=req.evictions)
        self._note('evictions')
        # FRONT of the queue: an evicted sequence restarts before any new
        # arrival — bounded starvation, deterministic regeneration
        self._queue.appendleft(req)

    def _handle_device_failure(self, exc):
        """A failed device call may have consumed the donated pool: fail
        every active sequence, release their pages, rebuild the pool."""
        with self._cv:
            failed = []
            for i, slot in enumerate(self._slots):
                if slot is not None:
                    failed.append(slot.req)
                    self._free_slot_locked(i)
            if self._prefix is not None:
                # cached KV lives in the pool being rebuilt: drop it all
                self._prefix.clear()
            # a step in flight computed on the pool that failed: its rows'
            # sequences are failed here, its tokens are never read
            self._inflight = None
            self._pool = self._init_pool()
            self._update_gauges_locked()
            self._cv.notify_all()
        for r in failed:
            r.rec.finish('error', exc)
            if r.future._finish(exc):
                self._note('failed')

    # ---- prefix cache knobs ----------------------------------------------
    @property
    def prefix_cache(self):
        """The engine's :class:`~.prefix_cache.PrefixCache` (None when
        disabled)."""
        return self._prefix

    def set_prefix_capacity(self, capacity_pages):
        """Bound prefix-cache residency to ``capacity_pages`` pool pages
        (None lifts the bound) — the ModelHost per-model knob. Evicts LRU
        leaves immediately when already over."""
        if self._prefix is None:
            return
        with self._lock:
            self._prefix.set_capacity(capacity_pages)
            self._update_gauges_locked()

    def clear_prefix_cache(self):
        """Release every cached page back toward the allocator (pages also
        mapped by live slots free when those slots retire). Returns the
        number of entries dropped."""
        if self._prefix is None:
            return 0
        with self._lock:
            n = self._prefix.clear()
            self._update_gauges_locked()
            return n

    # ---- observability ---------------------------------------------------
    def stats(self):
        elapsed = max(self._clock() - self._start_t, 1e-9)

        def pct(h, q):
            v = h.percentile(q)
            return round(v, 3) if v is not None else 0.0

        with self._lock:
            active = sum(1 for s in self._slots if s is not None)
            depth = len(self._queue)
            free_pages = sum(a.free_pages for a in self._allocs.values())
            state_bytes, page_bytes = self._held_bytes_locked()
        out = dict(self._n)
        out.update({
            'active_slots': active,
            'queue_depth': depth,
            'free_pages': free_pages,
            'num_slots': self.num_slots,
            'page_size': self.page_size,
            'num_pages': self.num_pages,
            # what the busy slots hold now: per-slot state (none but in a
            # family with such a kind) and the pages in use, in bytes
            'state_bytes': state_bytes,
            'page_bytes': page_bytes,
            'state_bytes_per_slot': self._state_bytes_per_slot,
            'prefill_width': self.prefill_width,
            'prefill_widths': self.prefill_widths,
            'traces': self._trace_count,
            'tokens_per_sec': round(self._n['tokens'] / elapsed, 2),
            'prefill_ms_p50': pct(self._h['prefill'], 50),
            'prefill_ms_p99': pct(self._h['prefill'], 99),
            'decode_step_ms_p50': pct(self._h['step'], 50),
            'decode_step_ms_p99': pct(self._h['step'], 99),
            'ttft_ms_p50': pct(self._h['ttft'], 50),
            'ttft_ms_p99': pct(self._h['ttft'], 99),
            'circuit_state': self._breaker.state,
            'precision': self._precision,
            'param_bytes': dict(self._param_bytes),
            'warmed': self._warmed,
            'uptime_s': round(elapsed, 3),
        })
        out['prefix'] = (self._prefix.stats()
                         if self._prefix is not None else None)
        out['mesh'] = (self._mesh_ctx.describe()
                       if self._mesh_ctx is not None else None)
        return out
