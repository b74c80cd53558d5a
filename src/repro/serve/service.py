"""Continuous-batching generate service on the device-resident scheduler.

The seed's ``launch/serve.py`` was a host-driven static-batch loop: prefill
a fixed batch, decode until the *slowest* request finishes, repeat.  This
module replaces it with a persistent service in the BatchGenerateService
mold (SHARK-Engine's ``service_v1``): an admission queue feeding a fixed
set of batch slots, requests joining and leaving mid-stream, and
batch-shape-specialized jitted entry points.  The QuickSched machinery is
not beside the serving path — it *is* the serving path:

* **Admission is a conflict round.**  Arriving requests take pages from
  the :class:`~repro.serve.blockpool.BlockPool` free list; the batch
  lowers through ``core.plan.lower`` as one PREFILL task per request
  locking its pages, must prove conflict-free (single round, one
  write-coloring phase), and then *executes through the ``rounds``
  backend* — ``BatchSpec(TT_PREFILL).run_one`` is the jitted prefill
  entry point that writes the prompt KV into the request's pages.
* **Decode is an engine task family.**  Each service tick lowers the
  active slots as DECODE tasks (one locked state resource per slot) and
  runs them through the ``engine`` backend: ``BatchSpec.encode`` emits
  ``[DECODE, slot, pos]`` descriptor rows and the family's
  :class:`~repro.core.backends.EngineHooks` round function decodes every
  slot in one jitted dispatch per tick.  *Which* round function depends
  on a capability probe of the backend registry
  (``get_backend("engine").compiled_kernels()``):

  - ``kernel`` — the paged-attention megakernel
    (``kernels/paged_attention``) walks each slot's page table
    *in-kernel* with an online softmax over only the pages the slot
    occupies and writes the new K/V cell through aliased refs — zero
    gather, zero scatter (natively compiled backends; forceable
    elsewhere, where it runs in Pallas interpret mode for conformance);
  - ``bounded`` — the jitted gather fallback, window-bounded: it
    gathers/attends only ``ceil((max active pos + 1)/page_size)`` pages
    per slot (a per-tick static from the descriptor positions), keeping
    the work ∝ occupied pages contract on hosts without compiled Pallas
    (the CPU/CI default);
  - ``gather`` — PR 6's full-``max_seq``-window path, kept as the
    conformance oracle the other two are pinned against token-for-token
    (and the only path for the non-paged SSM family).

* **Sampling is part of the decode family's buffers.**  Greedy argmax is
  the default and the conformance oracle; :class:`SamplingParams` with
  ``temperature > 0`` (plus optional top-k) threads one PRNG key per slot
  through the engine buffers — re-seeded per request from
  ``fold_in(seed, rid)`` at admission, split once per sampled token — so
  a request's token stream is deterministic under a fixed seed no matter
  how requests interleave.
* **The plan cache is the compiled-module registry.**  Admission and
  decode graphs are canonical (structure depends only on the batch
  shape), so ``core.plan``'s structural-hash cache maps each batch shape
  to its lowered plan, and the engine's segment-runner jit cache maps
  each plan layout to a compiled executable — the ``prefill_bs{n}`` /
  ``decode_bs{n}`` entry-point dicts of SHARK's service, derived instead
  of hand-registered (asserted via ``plan_cache_info()`` in
  ``tests/test_serve.py``).

**Robustness (DESIGN.md §Robustness).**  The same invariant that makes
continuous batching correct — exclusively lockable resources let
conflicting tasks run in *any order*, just not concurrently — is what
makes failure recovery safe: a preempted request's pages go back to the
pool intact and its PREFILL/DECODE tasks are simply re-lowered later as
another conflict round.  The service's failure model:

* **Lifecycle control** — per-request deadlines (absolute, on the
  service's virtual clock) and :meth:`GenerateService.cancel`; both
  evict active victims through :meth:`_preempt`, which scatters the slot
  out of the device-resident engine buffers, returns its pages to the
  free list (conservation asserted), and either requeues the request for
  re-admission (its prefix — prompt + tokens so far — is recomputed
  through the normal prefill family) or retires it terminally
  (``cancelled`` / ``deadline_exceeded``).
* **Guarded decode** — with ``guard=True`` (default) every decode round
  also writes a per-slot finiteness flag (``isfinite`` over the round's
  logits).  A slot that trips it is retried once, in-tick, on the
  ``gather`` reference round function; a slot that trips the retry too
  is preempted and re-admitted.  Repeated faults additionally *degrade*
  the per-tick round function down the capability ladder
  (kernel → bounded → gather) with exponential backoff before promoting
  back — PR 8's one-shot static probe generalized into a per-tick
  decision.
* **Chaos harness** — a seeded :class:`~repro.serve.faults.FaultPlan`
  threaded through :meth:`step` makes every path above deterministically
  reachable (``tests/test_faults.py``, the CI chaos smoke).

Every transition is metered (``serve.preemptions`` / ``serve.retries`` /
``serve.rejected`` / ``serve.deadline_exceeded`` / ``serve.cancelled``)
and traced (``request.preempted`` spans, counter tracks) through the
``repro.obs`` registry and Perfetto export.  The host path of a tick is
spanned where the work happens — ``serve.step`` around the tick,
``serve.admit`` with a ``serve.prefill`` per prefill group,
``serve.decode``, and a ``serve.sync`` around each device→host read
(counted exactly in ``serve.host_syncs``) — and an enabled tracer
mirrors those spans onto the profiler's clock.

Continuous-batched decode is token-for-token identical to the sequential
``serving.prefill``/``decode_step`` reference per request (conformance
tier in ``tests/test_serve.py``): prefill is the same B=1 call the
reference makes, batched paged decode matches the reference bitwise
(dense) or to float tolerance below greedy-argmax sensitivity (MLA/SSM),
and stale contents of reused pages are fully masked beyond ``pos``.
"""

from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backends import EngineHooks, run_plan
from repro.core.graph import QSched
from repro.core.plan import BatchSpec, lower
from repro.models import serving as serving_mod
from repro.obs import trace as _trace
from repro.obs.metrics import MetricsRegistry

from .blockpool import AdmissionConflict, TT_PREFILL, BlockPool
from .faults import FaultPlan

TT_DECODE = 1       # task type of the decode family
ENG_DECODE = 1      # engine descriptor row etype for a decode item

SUPPORTED_FAMILIES = ("dense", "moe", "ssm")
DECODE_PATHS = ("auto", "kernel", "bounded", "gather")
# capability ladder, fastest first — the degrade walk moves right
DECODE_LADDER = ("kernel", "bounded", "gather")

# guard-flag lane values (one int32 per slot in the engine buffers)
FLAG_OK = 0         # round produced finite logits
FLAG_FAULT = 1      # finiteness check tripped
FLAG_POISON = 2     # armed by chaos injection: round NaNs this slot's logits

# terminal request states (Request.status; "queued"/"active" are transient)
ST_DONE = "done"
ST_CANCELLED = "cancelled"
ST_DEADLINE = "deadline_exceeded"
TERMINAL_STATES = (ST_DONE, ST_CANCELLED, ST_DEADLINE)


class QueueFull(RuntimeError):
    """``submit()`` refused: the admission queue is at ``max_queue``."""

    def __init__(self, msg: str, *, queue_depth: int, max_queue: int):
        super().__init__(msg)
        self.queue_depth = queue_depth
        self.max_queue = max_queue


class ServiceStalled(RuntimeError):
    """``run_until_complete`` exhausted its step budget with requests
    still in flight.  Carries the diagnostic snapshot (queue depth,
    active slots, last tick that made progress) instead of failing
    silently — a stall is an operational bug (pool too small for a
    queued request, a fault loop, a budget set too low), and the
    snapshot says which."""

    def __init__(self, msg: str, *, queue_depth: int, active_slots: int,
                 last_progress_tick: int, steps: int):
        super().__init__(msg)
        self.queue_depth = queue_depth
        self.active_slots = active_slots
        self.last_progress_tick = last_progress_tick
        self.steps = steps


@dataclass(frozen=True)
class SamplingParams:
    """How next tokens are chosen from decode logits.

    The default (``temperature == 0``) is greedy argmax — the conformance
    oracle, bitwise-independent of the PRNG buffer.  ``temperature > 0``
    samples from the (optionally top-k-truncated) tempered distribution
    with one threefry key per slot threaded through the engine buffers;
    ``seed`` plus the request id fully determine a request's stream."""
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0


@dataclass
class Request:
    """One generation request moving through the service.  The ``t_*``
    timestamps (submit → admit → first token → complete, on the service's
    virtual clock) are always recorded — they feed the TTFT/latency
    histograms and, when a tracer is enabled, the per-request lifecycle
    spans.  ``status`` walks queued → active → one of
    :data:`TERMINAL_STATES` (a preempted request goes back to queued);
    ``deadline_s`` is absolute on the service clock, ``None`` = no
    deadline."""
    rid: int
    prompt: np.ndarray                 # (plen,) int32
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    pages: List[int] = field(default_factory=list)
    slot: int = -1
    pos: int = 0
    done: bool = False
    status: str = "queued"
    deadline_s: Optional[float] = None
    preemptions: int = 0
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0

    @property
    def tokens(self) -> List[int]:
        return list(self.generated)

    @property
    def ttft_s(self) -> float:
        """Submit → first token (0.0 until the first token exists)."""
        return self.t_first - self.t_submit if self.t_first else 0.0

    @property
    def latency_s(self) -> float:
        """Submit → retire (0.0 until the request completes)."""
        return self.t_done - self.t_submit if self.t_done else 0.0

    def feed_tokens(self) -> np.ndarray:
        """What (re-)admission prefills: the original prompt plus every
        token generated so far — a preempted request's prefix is
        recomputed through the normal prefill family, and greedy prefill
        of this feed reproduces exactly the token its evicted decode
        would have produced next."""
        if not self.generated:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])

    @property
    def total_positions(self) -> int:
        """Cache positions the request can ever touch (constant across
        preemptions: generated tokens move from budget to feed)."""
        return int(self.prompt.size) + self.max_new_tokens - 1


def _decode_row_access(row: Sequence[int]) -> Tuple[Tuple, Tuple]:
    """A decode item reads and writes only its own slot's pages/state, so
    the slot id is the state-row key: distinct slots never collide and
    every decode round colors to one grid-parallel phase."""
    key = ("slot", int(row[1]))
    return ((key,), (key,))


def _finish_decode(leaves, pt, tok, pos, keys, flags, slots, p_b, logits,
                   sampling: SamplingParams, guard: bool):
    """Common decode-round tail: pick next tokens and advance the slot
    state.  Greedy leaves the key buffer untouched (bitwise oracle).

    With ``guard`` the tail also (a) honors the chaos poison lane —
    a slot whose flag was armed to :data:`FLAG_POISON` gets its logits
    NaNed *here, inside the jitted round*, so injected faults flow
    through the identical detection path an organic NaN would — and
    (b) writes the per-slot finiteness verdict back into the flags
    buffer, which the service reads once per tick."""
    if guard:
        poisoned = flags[slots] == FLAG_POISON
        logits = jnp.where(poisoned[:, None], jnp.nan,
                           logits.astype(jnp.float32))
        ok = jnp.isfinite(logits).all(axis=-1)
        flags = flags.at[slots].set(
            jnp.where(ok, FLAG_OK, FLAG_FAULT).astype(jnp.int32))
    nxt, new_keys = serving_mod.sample_tokens(
        logits, keys[slots], sampling.temperature, sampling.top_k)
    if sampling.temperature > 0.0:
        keys = keys.at[slots].set(new_keys)
    return (leaves, pt, tok.at[slots].set(nxt),
            pos.at[slots].set(p_b + 1), keys, flags)


def _make_decode_round_fn(cfg, paged: bool, page_size: int, max_pages: int,
                          sampling: SamplingParams,
                          guard: bool) -> Callable:
    """The full-window gather round function — PR 6's path, now the
    conformance oracle (``decode_path="gather"``), the retry/degrade
    floor of the fallback ladder, and the only path for the non-paged
    SSM family.  Layout: ``desc[i] = [ENG_DECODE, slot, pos]``; buffers =
    ``(pool leaves, page_tables, tok, pos, keys, flags)``; statics =
    ``(params,)``.  Stable object per service, so the engine's jitted
    segment runners cache per batch shape."""

    def decode_round(desc, bounds, statics, buffers):
        del bounds                     # single write-colored phase
        params = statics[0]
        leaves, pt, tok, pos, keys, flags = buffers
        slots = desc[:, 1]
        p_b = desc[:, 2]
        bs = desc.shape[0]
        ptb = pt[slots]                                     # (bs, MP)
        if paged:
            cache = {
                k: leaf[:, ptb].reshape(
                    (leaf.shape[0], bs, max_pages * page_size)
                    + leaf.shape[3:])
                for k, leaf in leaves.items()}
        else:
            cache = {k: leaf[:, ptb[:, 0]] for k, leaf in leaves.items()}
        logits, new_cache = serving_mod.decode_step(
            params, cfg, cache, tok[slots][:, None], p_b)
        out = dict(leaves)
        if paged:
            # the step wrote exactly position p_b of each slot's cache:
            # scatter that one (page, offset) cell back into the pool
            page_ids = jnp.take_along_axis(
                ptb, (p_b // page_size)[:, None], axis=1)[:, 0]
            off = p_b % page_size
            bidx = jnp.arange(bs)
            for k, leaf in leaves.items():
                val = new_cache[k][:, bidx, p_b]            # (L, bs, ...)
                out[k] = leaf.at[:, page_ids, off].set(val)
        else:
            sid = ptb[:, 0]
            for k, leaf in leaves.items():
                out[k] = leaf.at[:, sid].set(new_cache[k])
        return _finish_decode(out, pt, tok, pos, keys, flags, slots, p_b,
                              logits, sampling, guard)

    return decode_round


def _make_bounded_decode_round_fn(cfg, page_size: int,
                                  sampling: SamplingParams,
                                  guard: bool) -> Callable:
    """Window-bounded gather round function (``decode_path="bounded"``,
    the default where Pallas is interpret-only): identical math to the
    full-window path, but it gathers/attends only the first ``n_walk``
    pages per slot, where ``n_walk = max(pos)//page_size + 1`` over the
    round is carried as the *shape* of a dummy static
    (``statics = (params, walk_token)``) so the engine re-specializes
    exactly when the page-walk bound grows — work stays ∝ occupied pages,
    like the kernel.  Bitwise-equal to the full window: every truncated
    position is masked to ``-inf`` there anyway."""

    def decode_round(desc, bounds, statics, buffers):
        del bounds
        params, walk = statics
        n_walk = walk.shape[0]         # static page-walk bound this round
        leaves, pt, tok, pos, keys, flags = buffers
        slots = desc[:, 1]
        p_b = desc[:, 2]
        bs = desc.shape[0]
        win = pt[slots][:, :n_walk]                         # (bs, n_walk)
        cache = {
            k: leaf[:, win].reshape(
                (leaf.shape[0], bs, n_walk * page_size) + leaf.shape[3:])
            for k, leaf in leaves.items()}
        logits, new_cache = serving_mod.decode_step(
            params, cfg, cache, tok[slots][:, None], p_b)
        page_ids = jnp.take_along_axis(
            win, (p_b // page_size)[:, None], axis=1)[:, 0]
        off = p_b % page_size
        bidx = jnp.arange(bs)
        out = {k: leaf.at[:, page_ids, off].set(
                   new_cache[k][:, bidx, p_b])
               for k, leaf in leaves.items()}
        return _finish_decode(out, pt, tok, pos, keys, flags, slots, p_b,
                              logits, sampling, guard)

    return decode_round


def _make_paged_decode_round_fn(cfg, page_size: int,
                                sampling: SamplingParams,
                                guard: bool) -> Callable:
    """The paged-attention megakernel round function
    (``decode_path="kernel"``): hand the pool leaves, page-table rows and
    descriptor positions straight to ``serving.decode_step_paged``, which
    walks each slot's pages in-kernel and writes the new cell through
    aliased refs — no gather, no scatter, no ``max_seq``-shaped
    intermediate."""

    def decode_round(desc, bounds, statics, buffers):
        del bounds
        params = statics[0]
        leaves, pt, tok, pos, keys, flags = buffers
        slots = desc[:, 1]
        p_b = desc[:, 2]
        logits, new_leaves = serving_mod.decode_step_paged(
            params, cfg, leaves, pt[slots], tok[slots][:, None], p_b,
            page_size=page_size)
        return _finish_decode(new_leaves, pt, tok, pos, keys, flags, slots,
                              p_b, logits, sampling, guard)

    return decode_round


class GenerateService:
    """Continuous-batching serving engine over a paged block pool.

    ``max_batch`` is the number of concurrent decode slots, ``max_seq``
    the per-request cache capacity (prompt + generated - 1 positions must
    fit), ``page_size`` the positions per pool page.  ``n_pages``
    defaults to exactly enough pages to fill every slot
    (``max_batch * max_seq / page_size``); set it lower to make paging
    pressure the admission bottleneck.

    Robustness knobs: ``max_queue`` bounds the admission queue
    (``submit`` raises :class:`QueueFull` past it); ``deadline_ms`` is a
    default per-request deadline (``submit(deadline_ms=...)`` overrides);
    ``guard`` enables the post-round finiteness check and the
    retry/degrade/preempt ladder; ``faults`` installs a
    :class:`~repro.serve.faults.FaultPlan` (requires ``guard``)."""

    def __init__(self, params: Any, cfg, *, max_batch: int = 4,
                 max_seq: int = 64, page_size: int = 8,
                 n_pages: Optional[int] = None, nr_lanes: int = 1,
                 decode_path: str = "auto",
                 sampling: Optional[SamplingParams] = None,
                 max_queue: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 guard: bool = True,
                 faults: Optional[FaultPlan] = None):
        if cfg.family not in SUPPORTED_FAMILIES:
            raise ValueError(
                f"GenerateService supports families {SUPPORTED_FAMILIES}, "
                f"not {cfg.family!r} (extra per-request inputs / trunk+"
                f"shared split not wired up yet)")
        if decode_path not in DECODE_PATHS:
            raise ValueError(
                f"decode_path must be one of {DECODE_PATHS}, "
                f"not {decode_path!r}")
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None)")
        self.params = params
        self.cfg = cfg
        self.paged = cfg.family != "ssm"
        self.sampling = sampling or SamplingParams()
        self.guard = bool(guard)
        # capability probe, not platform sniffing: the kernel path wins
        # only where the engine backend compiles Pallas natively
        if not self.paged:
            decode_path = "gather"     # SSM state is O(1) — nothing paged
        elif decode_path == "auto":
            from repro.core.backends import get_backend
            decode_path = ("kernel"
                           if get_backend("engine").compiled_kernels()
                           else "bounded")
        self.decode_path = decode_path
        if self.paged and max_seq % page_size != 0:
            raise ValueError("max_seq must be a multiple of page_size")
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.nr_lanes = nr_lanes
        self.max_pages = max_seq // page_size if self.paged else 1
        if n_pages is None:
            n_pages = max_batch * self.max_pages
        self.pool = BlockPool(n_pages, page_size, cfg=cfg)
        self.max_queue = max_queue
        self.deadline_ms = deadline_ms

        # slot state lives on device between steps (page table, last
        # token, position, sampling key, guard flag) — the engine's
        # buffers are passed straight through with no per-step
        # host<->device conversion
        self._pt = jnp.zeros((max_batch, self.max_pages), jnp.int32)
        self._tok = jnp.zeros((max_batch,), jnp.int32)
        self._pos = jnp.zeros((max_batch,), jnp.int32)
        # one raw threefry key row per slot; admission overwrites the
        # slot's row with fold_in(seed, rid) so a request's sample stream
        # depends only on (seed, rid), not on scheduling history
        self._keys = jnp.zeros((max_batch, 2), jnp.uint32)
        self._flags = jnp.zeros((max_batch,), jnp.int32)
        self._free_slots: List[int] = list(range(max_batch - 1, -1, -1))
        self._active: Dict[int, Request] = {}
        self._queue: Deque[Request] = deque()
        self._requests: Dict[int, Request] = {}    # rid -> live request
        self._next_rid = 0

        # batch-shape-specialized jitted entry points: prefill per
        # (prompt length, batch size) — SHARK's prefill_bs{n} dict, keyed
        # by shape instead of symbol name, with same-plen admissions
        # sharing one batched entry point; decode specializations live in
        # the engine's segment-runner jit cache, one per batch size seen
        self._prefill_fns: Dict[Tuple[int, int], Callable] = {}
        self.decode_batch_sizes_seen: set = set()

        self.registry = {
            TT_PREFILL: BatchSpec(run_one=self._run_prefill,
                                  run_batch=self._run_prefill_batch),
            TT_DECODE: BatchSpec(run_one=self._no_host_decode,
                                 encode=self._encode_decode),
        }
        # degrade ladder: the selected path plus everything below it.
        # One EngineHooks (and one stable round_fn object, for the jit
        # caches) per rung; the last rung is always the gather oracle —
        # it is also the in-tick retry path.
        self._ladder: Tuple[str, ...] = (
            ("gather",) if not self.paged
            else DECODE_LADDER[DECODE_LADDER.index(self.decode_path):])
        self._level = 0                 # current rung (0 = selected path)
        self._fault_streak = 0          # consecutive faulted ticks
        self._cooldown = 0              # clean ticks before promotion
        self._hooks_by_path: Dict[str, EngineHooks] = {
            path: self._make_hooks(path) for path in self._ladder}

        # robustness bookkeeping
        self._faults: Optional[FaultPlan] = None
        self.faults_fired: List[Tuple[int, Any, bool]] = []
        self.faulted_rids: set = set()   # preempted / cancelled / expired
        self.retried_rids: set = set()   # recovered by the in-tick retry
        self._poison_budget: Dict[int, int] = {}   # slot -> armed rounds
        self._admission_fault = False
        self._skew = 0.0                 # virtual-clock offset (stalls)
        self._last_progress_tick = -1
        self.inject(faults)

        # per-service metrics registry (DESIGN.md §Observability): exact
        # lifecycle counters (the old ad-hoc stats dict, now typed),
        # occupancy/depth gauges sampled every tick, TTFT + end-to-end
        # latency histograms.  `stats` stays dict-shaped for callers.
        self.metrics = MetricsRegistry()
        self._counters = {k: self.metrics.counter(f"serve.{k}")
                          for k in ("submitted", "admitted", "retired",
                                    "steps", "decode_items",
                                    "generated_tokens", "pages_attended",
                                    "preemptions", "retries", "rejected",
                                    "deadline_exceeded", "cancelled",
                                    "faults_injected", "host_syncs")}
        self._g_pages = self.metrics.gauge("serve.pages_in_use")
        self._g_queue = self.metrics.gauge("serve.queue_depth")
        self._g_active = self.metrics.gauge("serve.active_slots")
        self._g_level = self.metrics.gauge("serve.degrade_level")
        self._h_ttft = self.metrics.histogram("serve.ttft_s")
        self._h_latency = self.metrics.histogram("serve.latency_s")

    def _make_hooks(self, path: str) -> EngineHooks:
        if path == "kernel":
            round_fn = _make_paged_decode_round_fn(
                self.cfg, self.pool.page_size, self.sampling, self.guard)
        elif path == "bounded":
            round_fn = _make_bounded_decode_round_fn(
                self.cfg, self.pool.page_size, self.sampling, self.guard)
        else:
            round_fn = _make_decode_round_fn(
                self.cfg, self.paged, self.pool.page_size, self.max_pages,
                self.sampling, self.guard)
        return EngineHooks(
            arg_width=2,
            round_fn=round_fn,
            statics=functools.partial(self._statics_for, path),
            buffers=self._buffers,
            writeback=self._writeback,
            row_access=_decode_row_access,
            fuse_rounds=False,
            donate=False,
        )

    @property
    def stats(self) -> Dict[str, int]:
        """Exact lifecycle counts as a plain dict — backward-compatible
        view over the metrics registry (``tests/test_serve.py`` asserts
        these counts; ``GenerateService.metrics`` is the full registry)."""
        return {k: c.value for k, c in self._counters.items()}

    @property
    def decode_path_active(self) -> str:
        """The rung of the degrade ladder the next tick will run on
        (equals ``decode_path`` until a fault degrades it)."""
        return self._ladder[self._level]

    @property
    def hooks(self) -> EngineHooks:
        """EngineHooks for the currently active decode path."""
        return self._hooks_by_path[self.decode_path_active]

    def _now(self) -> float:
        """The service's virtual clock: the tracer clock plus any stall
        skew injected by the chaos harness.  Deadlines and request
        timestamps live on this clock so tests can expire deadlines
        without sleeping."""
        return _trace.now() + self._skew

    # -- public API ----------------------------------------------------------
    def inject(self, faults: Optional[FaultPlan]) -> None:
        """Install (or clear) a chaos plan.  Requires the decode guard:
        injected NaNs must flow through the real detection path."""
        if faults is not None and not self.guard:
            raise ValueError("chaos injection requires guard=True — "
                             "injected faults must hit the real "
                             "finiteness check")
        self._faults = faults

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               deadline_ms: Optional[float] = None) -> Request:
        """Queue one request.  Tokens arrive in ``Request.generated`` as
        the service steps; the first token comes from prefill.  Raises
        :class:`QueueFull` when a bounded queue is at capacity —
        back-pressure is the caller's problem, unbounded growth is
        nobody's solution."""
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self._counters["rejected"].inc()
            raise QueueFull(
                f"admission queue full ({len(self._queue)} >= "
                f"max_queue={self.max_queue})",
                queue_depth=len(self._queue), max_queue=self.max_queue)
        prompt = np.asarray(prompt, np.int32).ravel()
        if prompt.size < 1 or max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and max_new_tokens >= 1")
        positions = int(prompt.size) + max_new_tokens - 1
        if self.paged and positions > self.max_seq:
            raise ValueError(
                f"request needs {positions} cache positions, service "
                f"max_seq is {self.max_seq}")
        req = Request(self._next_rid, prompt, max_new_tokens)
        req.t_submit = self._now()
        eff = deadline_ms if deadline_ms is not None else self.deadline_ms
        if eff is not None:
            req.deadline_s = req.t_submit + eff / 1e3
        self._next_rid += 1
        self._queue.append(req)
        self._requests[req.rid] = req
        self._counters["submitted"].inc()
        self._g_queue.set(len(self._queue))
        return req

    def cancel(self, rid: int) -> bool:
        """Cancel a live request: a queued one retires immediately, an
        active one is preempted (pages reclaimed) and retires.  Returns
        False for unknown or already-terminal rids."""
        req = self._requests.get(rid)
        if req is None or req.done:
            return False
        if req.slot >= 0:
            self._preempt(req.slot, requeue=False, status=ST_CANCELLED,
                          reason="cancel")
        else:
            self._queue.remove(req)
            self._retire(req, ST_CANCELLED)
        return True

    def step(self) -> bool:
        """One service tick: fire scheduled faults, sweep deadlines,
        admit whatever fits (conflict-round prefill), then one guarded
        continuous-batched decode over every active slot.  Returns True
        while any request is queued or in flight."""
        with _trace.span("serve.step"):
            return self._tick()

    def _tick(self) -> bool:
        tick = self._counters["steps"].value
        before = (self._counters["admitted"].value,
                  self._counters["retired"].value)
        self._apply_faults(tick)
        self._sweep_deadlines()
        self._admit()
        slots = sorted(self._active)
        progressed = False
        if slots:
            # pages each slot's walk touches this tick (incl. the cell
            # being written) — what the kernel/bounded paths actually
            # read, and the honest work metric for the gather oracle too
            ps = self.pool.page_size
            pages = (sum(self._active[s].pos // ps + 1 for s in slots)
                     if self.paged else len(slots))
            with _trace.span("serve.decode", batch=len(slots), pages=pages):
                ok_slots = self._decode_tick(slots)
                self._counters["decode_items"].inc(len(slots))
                self._counters["pages_attended"].inc(pages)
                tok_h = self._read("tokens", self._tok)
                pos_h = self._read("positions", self._pos)
            for slot in ok_slots:
                req = self._active[slot]
                req.generated.append(int(tok_h[slot]))
                req.pos = int(pos_h[slot])
                self._counters["generated_tokens"].inc()
                progressed = True
            for slot in ok_slots:
                req = self._active[slot]
                if len(req.generated) >= req.max_new_tokens:
                    self._retire(req)
        self._counters["steps"].inc()
        self._sample_gauges()
        if (progressed
                or self._counters["admitted"].value > before[0]
                or self._counters["retired"].value > before[1]):
            self._last_progress_tick = tick
        return bool(self._active or self._queue)

    def run_until_complete(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if not self.step():
                return
        raise ServiceStalled(
            f"service did not drain in {max_steps} steps: "
            f"{len(self._queue)} queued, {len(self._active)} active, "
            f"last progress at tick {self._last_progress_tick} of "
            f"{self._counters['steps'].value}",
            queue_depth=len(self._queue), active_slots=len(self._active),
            last_progress_tick=self._last_progress_tick,
            steps=self._counters["steps"].value)

    def compiled_entry_points(self) -> Dict[str, List]:
        """The service's module registry: which specialized entry points
        exist (prefill by (prompt length, batch size), decode by batch
        size)."""
        return {"prefill_plens": sorted({p for p, _ in self._prefill_fns}),
                "prefill_shapes": sorted(self._prefill_fns),
                "decode_batch_sizes": sorted(self.decode_batch_sizes_seen)}

    def _read(self, what: str, x) -> np.ndarray:
        """Every device→host read of the tick path goes through here: it
        waits for the device, so it is counted (``serve.host_syncs``) and
        spanned (``serve.sync``, ``what`` names the read)."""
        self._counters["host_syncs"].inc()
        with _trace.span("serve.sync", what=what):
            return np.asarray(x)

    # -- fault application (chaos harness) -----------------------------------
    def _apply_faults(self, tick: int) -> None:
        if self._faults is None:
            return
        for ev in self._faults.events_at(tick):
            applied = True
            if ev.kind == "nan_decode":
                if self._active:
                    slots = sorted(self._active)
                    slot = slots[ev.victim % len(slots)]
                    self._poison_budget[slot] = max(
                        self._poison_budget.get(slot, 0), ev.sticky)
                else:
                    applied = False    # nothing decoding — fires as no-op
            elif ev.kind == "admission_fail":
                self._admission_fault = True
            elif ev.kind == "drop_prefill":
                self._prefill_fns.clear()
            elif ev.kind == "stall":
                self._skew += ev.skew_s
            if applied:
                self._counters["faults_injected"].inc()
            self.faults_fired.append((tick, ev, applied))

    def _arm_poison(self, slots: Sequence[int]) -> None:
        """Spend one round of each victim slot's poison budget by arming
        its guard flag to :data:`FLAG_POISON` — the jitted round tail
        NaNs the armed slots' logits (see ``_finish_decode``)."""
        if not self._poison_budget:
            return
        hit = [s for s in slots if self._poison_budget.get(s, 0) > 0]
        if not hit:
            return
        self._flags = self._flags.at[jnp.asarray(hit)].set(FLAG_POISON)
        for s in hit:
            self._poison_budget[s] -= 1
            if self._poison_budget[s] <= 0:
                del self._poison_budget[s]

    # -- deadlines -----------------------------------------------------------
    def _sweep_deadlines(self) -> None:
        now = self._now()
        expired_q = [r for r in self._queue
                     if r.deadline_s is not None and now >= r.deadline_s]
        for req in expired_q:
            self._queue.remove(req)
            self._retire(req, ST_DEADLINE)
        for slot in sorted(self._active):
            req = self._active[slot]
            if req.deadline_s is not None and now >= req.deadline_s:
                self._preempt(slot, requeue=False, status=ST_DEADLINE,
                              reason="deadline")

    # -- admission (conflict round + prefill family) -------------------------
    def _admit(self) -> int:
        batch: List[Request] = []
        while self._queue and self._free_slots:
            req = self._queue[0]
            need = self.pool.pages_needed(req.total_positions)
            if not self.pool.can_admit(need):
                break
            self._queue.popleft()
            req.t_admit = self._now()
            req.slot = self._free_slots.pop()
            req.pages = self.pool.alloc(need, owner=req.rid)
            batch.append(req)
        if not batch:
            return 0
        with _trace.span("serve.admit", batch=len(batch)):
            # lower the batch as a conflict round over canonical page
            # resources (single round + single coloring phase proven by
            # plan_admission), then execute the PREFILL family through the
            # rounds backend — run_one is the jitted prefill entry point
            try:
                if self._admission_fault:
                    self._admission_fault = False
                    raise AdmissionConflict(
                        "injected admission failure (chaos)")
                sched, plan = self.pool.plan_admission(
                    [r.pages for r in batch], TT_PREFILL, datas=batch,
                    nr_lanes=self.nr_lanes)
            except AdmissionConflict:
                # roll back: pages to the free list, slots returned, requests
                # requeued in arrival order — retried next tick.  The pool
                # must come out of the rollback conserving every page.
                for req in reversed(batch):
                    self.pool.free(req.pages)
                    req.pages = []
                    self._free_slots.append(req.slot)
                    req.slot = -1
                    self._queue.appendleft(req)
                self.pool.check_invariants()
                self._counters["retries"].inc(len(batch))
                return 0
            run_plan(sched, self.registry, "rounds", plan=plan)
            self._counters["admitted"].inc(len(batch))
            for req in batch:
                req.status = "active"
                if len(req.generated) >= req.max_new_tokens:
                    self._retire(req)      # prompt-only requests never decode
            return len(batch)

    def _run_prefill(self, tid: int, req: Request) -> None:
        self._prefill_group([req])

    def _run_prefill_batch(self, tids: Sequence[int],
                           reqs: Sequence[Request]) -> None:
        """Batched multi-request prefill: same-length feeds admitted in
        one conflict round share one jitted entry point (one forward pass
        over a ``(nb, plen)`` token block instead of nb B=1 calls)."""
        groups: Dict[int, List[Request]] = {}
        for req in reqs:
            groups.setdefault(len(req.feed_tokens()), []).append(req)
        for group in groups.values():
            self._prefill_group(group)

    def _prefill_group(self, reqs: List[Request]) -> None:
        feeds = [req.feed_tokens() for req in reqs]
        plen = int(feeds[0].size)
        nb = len(reqs)
        with _trace.span("serve.prefill", plen=plen, nb=nb):
            fn = self._prefill_fns.get((plen, nb))
            if fn is None:
                fn = self._prefill_fns[(plen, nb)] = self._make_prefill_fn(
                    plen, nb)
            np_p = self.pool.pages_needed(plen)
            # only the first ceil(plen/ps) pages hold prompt positions; the
            # rest of each request's pages fill one decode-scatter at a time
            page_ids = np.zeros((nb, np_p), np.int32)
            pt_rows = np.zeros((nb, self.max_pages), np.int32)
            slots = np.zeros((nb,), np.int32)
            base_key = jax.random.PRNGKey(self.sampling.seed)
            req_keys = np.stack(
                [self._read("keys", jax.random.fold_in(base_key, req.rid))
                 for req in reqs])
            for i, req in enumerate(reqs):
                page_ids[i] = req.pages[:np_p]
                pt_rows[i, :len(req.pages)] = req.pages
                slots[i] = req.slot
            tokens = np.stack(feeds)
            (tok0, self.pool.leaves, self._pt, self._tok, self._pos,
             self._keys) = fn(
                self.params, jnp.asarray(tokens), self.pool.leaves,
                jnp.asarray(page_ids), jnp.asarray(pt_rows),
                jnp.asarray(slots), jnp.asarray(req_keys), self._pt,
                self._tok, self._pos, self._keys)
            tok0_h = self._read("first_token", tok0)
            t = self._now()                # prefill yields the next token
            for i, req in enumerate(reqs):
                req.generated.append(int(tok0_h[i]))
                req.pos = plen
                if not req.t_first:
                    req.t_first = t
                self._active[req.slot] = req
                self._counters["generated_tokens"].inc()

    def _make_prefill_fn(self, plen: int, nb: int) -> Callable:
        cfg = self.cfg
        paged = self.paged
        ps = self.pool.page_size
        np_p = self.pool.pages_needed(plen)
        pad_to = np_p * ps - plen
        sampling = self.sampling

        @jax.jit
        def prefill_entry(params, tokens, leaves, page_ids, pt_rows,
                          slots, req_keys, pt, tok, pos, keys):
            logits, cache, _ = serving_mod.prefill(params, cfg, tokens)
            out = dict(leaves)
            if paged:
                for k, leaf in leaves.items():
                    c = cache[k]                     # (L, nb, plen, ...)
                    c = jnp.pad(c, [(0, 0), (0, 0), (0, pad_to)]
                                + [(0, 0)] * (c.ndim - 3))
                    c = c.reshape((c.shape[0], nb, np_p, ps) + c.shape[3:])
                    out[k] = leaf.at[:, page_ids].set(c.astype(leaf.dtype))
            else:
                for k, leaf in leaves.items():
                    out[k] = leaf.at[:, page_ids[:, 0]].set(
                        cache[k].astype(leaf.dtype))
            keys = keys.at[slots].set(req_keys)
            tok0, new_keys = serving_mod.sample_tokens(
                logits, keys[slots], sampling.temperature, sampling.top_k)
            if sampling.temperature > 0.0:
                keys = keys.at[slots].set(new_keys)
            return (tok0, out, pt.at[slots].set(pt_rows),
                    tok.at[slots].set(tok0), pos.at[slots].set(plen),
                    keys)

        return prefill_entry

    # -- decode (engine task family) -----------------------------------------
    def _decode_tick(self, slots: List[int]) -> List[int]:
        """One guarded decode round over ``slots``.  Runs the active
        ladder rung; with the guard on, reads the per-slot finiteness
        flags afterwards, retries any tripped slot once on the gather
        reference round function (restoring the slot's pre-round token /
        position / key from the immutable pre-round buffers), and
        preempts slots whose retry trips too.  Returns the slots whose
        tokens this tick are trustworthy."""
        prev = (self._tok, self._pos, self._keys)   # immutable snapshots
        self._arm_poison(slots)
        sched = self._decode_sched(slots)
        plan = lower(sched, self.nr_lanes)
        run_plan(sched, self.registry, "engine", plan=plan,
                 engine=self._hooks_by_path[self.decode_path_active])
        self.decode_batch_sizes_seen.add(len(slots))
        if not self.guard:
            return slots
        flags_h = self._read("flags", self._flags)
        bad = [s for s in slots if flags_h[s] != FLAG_OK]
        if not bad:
            self._note_clean_tick()
            return slots
        # faulted round: the victims' token/position/key advanced with
        # garbage — restore from the pre-round arrays (zero-copy: jax
        # arrays are immutable) and re-run just those slots on the
        # reference path.  The faulted round's KV-cell writes need no
        # undo: the retry rewrites the victims' cells at the same
        # (page, offset), and decode masks everything beyond pos.
        self._counters["retries"].inc(len(bad))
        self.retried_rids.update(self._active[s].rid for s in bad)
        self._note_fault_tick()
        idx = jnp.asarray(bad)
        self._tok = self._tok.at[idx].set(prev[0][idx])
        self._pos = self._pos.at[idx].set(prev[1][idx])
        self._keys = self._keys.at[idx].set(prev[2][idx])
        self._arm_poison(bad)          # sticky faults poison the retry too
        rsched = self._decode_sched(bad)
        run_plan(rsched, self.registry, "engine",
                 plan=lower(rsched, self.nr_lanes),
                 engine=self._hooks_by_path[self._ladder[-1]])
        flags_h = self._read("flags", self._flags)
        still_bad = [s for s in bad if flags_h[s] != FLAG_OK]
        for s in still_bad:
            # restore once more so the requeued request's host state is
            # consistent (its generated list never saw this tick)
            self._tok = self._tok.at[s].set(prev[0][s])
            self._pos = self._pos.at[s].set(prev[1][s])
            self._keys = self._keys.at[s].set(prev[2][s])
            self._preempt(s, requeue=True, reason="nan_decode")
        return [s for s in slots if s not in still_bad]

    def _note_fault_tick(self) -> None:
        """Degrade one rung with exponential backoff: each consecutive
        faulted tick doubles the clean-tick cooldown a rung must survive
        before promotion back up the ladder."""
        self._fault_streak += 1
        self._cooldown = min(2 ** self._fault_streak, 256)
        if self._level < len(self._ladder) - 1:
            self._level += 1
        self._g_level.set(self._level)

    def _note_clean_tick(self) -> None:
        if self._cooldown > 0:
            self._cooldown -= 1
            return
        if self._level > 0:
            self._level -= 1           # promote one rung per clean window
            self._g_level.set(self._level)
        else:
            self._fault_streak = 0

    def _decode_sched(self, slots: Sequence[int]) -> QSched:
        """Canonical decode graph: one DECODE task per active slot locking
        one state resource under a root.  The payload carries ``(slot,
        pos)`` — task *data* is excluded from the structural hash, so the
        plan cache key still depends only on the batch size even though
        positions change every tick."""
        s = QSched()
        root = s.addres()
        for slot in slots:
            rid = s.addres(parent=root)
            tid = s.addtask(type=TT_DECODE,
                            data=(int(slot), int(self._active[slot].pos)))
            s.addlock(tid, rid)
        return s

    def _encode_decode(self, tid: int, data: Tuple[int, int]):
        slot, pos = data
        return [(ENG_DECODE, int(slot), int(pos))]

    def _no_host_decode(self, tid: int, data) -> None:
        raise NotImplementedError(
            "the decode family is device-resident; run it through the "
            "'engine' backend")

    def _statics_for(self, path: str) -> Tuple:
        if path != "bounded":
            return (self.params,)
        # page-walk bound for this round, carried as the SHAPE of a dummy
        # static so the engine's jit cache re-specializes exactly when the
        # bound grows (descriptor *values* never retrace; shapes do)
        mx = max((r.pos for r in self._active.values()), default=0)
        n_walk = min(self.max_pages, mx // self.pool.page_size + 1)
        return (self.params, jnp.zeros((n_walk,), jnp.int32))

    def _statics(self) -> Tuple:
        return self._statics_for(self.decode_path_active)

    def _buffers(self) -> Tuple:
        return (self.pool.leaves, self._pt, self._tok, self._pos,
                self._keys, self._flags)

    def _writeback(self, buffers: Tuple) -> None:
        (self.pool.leaves, self._pt, self._tok, self._pos,
         self._keys, self._flags) = buffers

    def _sample_gauges(self) -> None:
        """Sample occupancy/depth gauges and, when a tracer is enabled,
        emit them as counter-track samples — the page-pool occupancy,
        queue-depth and failure-counter time series in the Perfetto
        view."""
        in_use = self.pool.allocated
        self._g_pages.set(in_use)
        self._g_queue.set(len(self._queue))
        self._g_active.set(len(self._active))
        tr = _trace.get_tracer()
        if tr.enabled:
            t = _trace.now()
            tr.counter("serve.pages_in_use", in_use, t=t)
            tr.counter("serve.queue_depth", len(self._queue), t=t)
            tr.counter("serve.active_slots", len(self._active), t=t)
            tr.counter("serve.pages_attended",
                       self._counters["pages_attended"].value, t=t)
            for k in ("preemptions", "retries", "rejected",
                      "deadline_exceeded"):
                tr.counter(f"serve.{k}", self._counters[k].value, t=t)

    # -- eviction / retirement -----------------------------------------------
    def _preempt(self, slot: int, *, requeue: bool, status: str = ST_DONE,
                 reason: str = "") -> None:
        """Evict the request occupying ``slot``: scatter the victim out
        of the device-resident engine buffers (its page-table row, token,
        position, key and guard flag are zeroed so a stale row can never
        alias a later tenant), return its pages to the pool free list
        with conservation asserted, then either requeue it for
        re-admission (the conflict model guarantees re-running its
        prefill later is order-safe) or retire it with ``status``."""
        req = self._active.pop(slot)
        t0 = self._now()
        self._pt = self._pt.at[slot].set(0)
        self._tok = self._tok.at[slot].set(0)
        self._pos = self._pos.at[slot].set(0)
        self._keys = self._keys.at[slot].set(0)
        self._flags = self._flags.at[slot].set(FLAG_OK)
        self.pool.free(req.pages)
        req.pages = []
        self.pool.check_invariants()   # page conservation, every eviction
        self._free_slots.append(slot)
        self._poison_budget.pop(slot, None)
        req.slot = -1
        req.pos = 0
        req.preemptions += 1
        self._counters["preemptions"].inc()
        self.faulted_rids.add(req.rid)
        tr = _trace.get_tracer()
        if tr.enabled:
            tr.event_span("request.preempted", t0, self._now(),
                          lane=f"req {req.rid}", process="requests",
                          rid=req.rid, reason=reason, requeue=requeue,
                          tokens_so_far=len(req.generated))
        if requeue:
            req.status = "queued"
            self._queue.appendleft(req)
            self._g_queue.set(len(self._queue))
        else:
            self._retire(req, status)

    def _retire(self, req: Request, status: str = ST_DONE) -> None:
        assert status in TERMINAL_STATES
        if req.pages:
            self.pool.free(req.pages)
            req.pages = []
        if req.slot >= 0:
            self._active.pop(req.slot, None)
            self._free_slots.append(req.slot)
            req.slot = -1
        req.status = status
        req.done = True
        req.t_done = self._now()
        if not req.t_first:            # never produced a token
            req.t_first = req.t_done
        self._requests.pop(req.rid, None)
        self._counters["retired"].inc()
        if status == ST_CANCELLED:
            self._counters["cancelled"].inc()
            self.faulted_rids.add(req.rid)
        elif status == ST_DEADLINE:
            self._counters["deadline_exceeded"].inc()
            self.faulted_rids.add(req.rid)
        self._h_ttft.observe(req.ttft_s)
        self._h_latency.observe(req.latency_s)
        tr = _trace.get_tracer()
        if tr.enabled:
            # request lifecycle as nested-looking phases on one lane per
            # request: queued (submit->admit), prefill (admit->first
            # token), decode (first token->retire).  Stages a request
            # never reached (cancelled in queue, expired before a token)
            # simply emit no span.
            lane = f"req {req.rid}"
            kw = dict(lane=lane, process="requests", rid=req.rid)

            def span(name, t0, t1, **extra):
                if t1 >= t0 > 0:
                    tr.event_span(name, t0, t1, **kw, **extra)

            span("request.queued", req.t_submit, req.t_admit or req.t_done)
            span("request.prefill", req.t_admit, req.t_first,
                 prompt_len=int(req.prompt.size))
            if req.t_done > req.t_first:
                span("request.decode", req.t_first, req.t_done,
                     tokens=len(req.generated))
            span("request", req.t_submit, req.t_done, status=status,
                 ttft_s=req.ttft_s, latency_s=req.latency_s)
