"""Prefix-shared decode engine (the paper's vLLM-integration analogue).

Continuous-batching decode loop with CoDec as the attention backend,
organised as a small per-step state machine — **admit → prefill →
decode → evict** (DESIGN.md §6) — so the engine survives and exploits
memory pressure instead of raising ``MemoryError``:

* requests enter a FCFS **waiting queue**; admission is gated by a page
  watermark and a cost-model prefill budget (``core.scheduler.
  AdmissionController``), and long prompts are prefilled in **chunks**
  interleaved with decode steps;
* prompts are radix-inserted into a ``PrefixForest``; already-cached
  nodes are *not* recomputed (prefill prefix reuse) — only the new leaf's
  KV is computed, attending to the gathered cached prefix;
* decode attention = **frozen CoDec plan** over all full pages (rebuilt
  exactly when ``core.plan.plan_key`` changes: batch membership, path
  structure, or a leaf crossing a page boundary — the paper's "reuse a
  division plan for multiple decoding steps") POR-merged with a **tail
  attention** over each request's growing last page;
* when the paged pool runs dry the engine **preempts and recomputes**:
  the victim with the fewest generated tokens releases its non-shared
  pages, its shared prefix nodes stay pinned (``node.meta["pins"]``
  refcounts) and it re-enters the queue to be re-prefilled from the
  radix-cached prefix;
* Mamba layers (hybrid archs) keep per-request recurrent state, with
  end-of-node state caching so shared prefixes are also not recomputed
  for SSM mixers (the SSM analogue of prefix caching — see DESIGN.md §5);
* decode attention backends are resolved by NAME through
  ``kernels.registry`` (``codec-pallas`` / ``codec-xla`` / ``hydragen``
  prefix-shared, ``flash`` per-request baseline, ``ref`` oracle); the
  backend's ``prepare(plan)`` output is cached across steps and its
  ``partials`` are POR-merged with the tail-page attention — see
  DESIGN.md §2–§3 for the contract;
* with ``fused=True`` the whole decode step — scanned layer stack, KV
  tail writes, backend partials, POR merge, FFN, unembed, sampling —
  runs as ONE jitted, donated, shape-bucketed device dispatch per token
  (``serving/step_fn.py``), with asynchronous dispatch: the host defers
  sampled tokens as placeholders and syncs only at plan-rebuild /
  admission / eviction / completion boundaries
  (``flush_tokens``, DESIGN.md §8); backends that cannot trace
  (``ref``) transparently fall back to the eager per-layer path;
* with ``speculative=`` (``True`` or a ``speculation.SpecConfig``) each
  decode step becomes a draft-propose / tree-verify / accept-rollback
  loop (DESIGN.md §10): a deterministic n-gram proposer grows a bounded
  draft tree of ordinary forest nodes under each request's leaf, ONE
  multi-query dispatch scores every branch head through the backend
  registry (``core.plan.build_verify_plan`` — sibling branches share
  all ancestor KV reads), greedy acceptance commits the longest
  matching path (KV moves from draft pages to the leaf tail) and
  rollback releases the rejected draft pages — so several tokens can
  commit per dispatch while the committed stream stays byte-identical
  to non-speculative greedy decode;
* with ``mesh=`` (a ``(data, model)`` jax mesh) the engine serves SPMD
  (DESIGN.md §9): the KV pool shards pages over ``data`` and heads
  over ``model`` (``distributed.ShardedKVPool``, per-shard allocator
  invariants), plans are partitioned per data shard with sequence
  splits cut at shard boundaries (``core.plan.build_sharded_plan``),
  and the fused step traces under ``shard_map`` with a cross-device
  POR butterfly merge (``distributed/step_fn.py``) — token streams
  stay byte-identical to the single-device engine at any mesh shape.

Under greedy decoding the token streams are independent of memory
pressure: a preempted-and-recomputed request produces exactly the same
tokens as in an unconstrained run (asserted by the differential test
harness).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import LayerKind, ModelConfig
from ..core import plan as plan_mod
from ..core import tree as tree_mod
from ..core.cost_model import CostModel
from ..core.scheduler import AdmissionController, AdmissionPolicy
from ..kernels import ops, ref as ref_mod, registry as registry_mod
from ..models import layers as L
from ..models import mamba as M
from ..models import transformer as T
from . import faults as faults_mod
from . import sampler, speculation as spec_mod, step_fn as step_fn_mod
from . import telemetry as telemetry_mod
from .cache import CachePolicy, PrefixCache
from .faults import EngineInvariantError, InjectedFault, ResourceExhausted
from .kv_cache import PagedKVPool

# request lifecycle states
WAITING, PREFILL, RUNNING, DONE = "waiting", "prefill", "running", "done"
# terminal failure states (DESIGN.md §12): the request is over, its KV
# released, and its stream closed via on_done(rid, reason)
CANCELLED, TIMED_OUT, FAILED = "cancelled", "timed_out", "failed"
TERMINAL = frozenset({DONE, CANCELLED, TIMED_OUT, FAILED})

# a sampled token that still lives in an un-synced device array
# (fused async dispatch); materialised by ``DecodeEngine.flush_tokens``
PENDING_DEVICE = "<device>"
_PLACEHOLDER = -1


class _Deferred:
    """One fused dispatch's sampled tokens, not yet on the host.

    ``patches`` records where each token was appended as a placeholder
    (request ``generated`` index + forest leaf token slot) so a later
    flush can write the real values in place.
    """

    __slots__ = ("tokens", "ok", "rows", "patches")

    def __init__(self, tokens, rows, ok=None):
        self.tokens = tokens          # (B_bucket,) device int32
        self.ok = ok                  # (B_bucket,) device bool, or None
        self.rows = rows              # request id per row
        self.patches = []             # (rid, row, gen_idx, node_id, tok_idx)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    generated: List[int] = dataclasses.field(default_factory=list)
    pending: Optional[int] = None      # sampled, not yet appended
    max_new: int = 16
    state: str = WAITING
    preemptions: int = 0
    computed_hwm: int = 0              # highest position this request ever computed
    pinned: List[int] = dataclasses.field(default_factory=list)
    kv_freed: bool = False             # done + KV reclaimed under pressure
    on_token: Optional[Any] = None     # streaming callback (rid, token)
    emitted: int = 0                   # tokens already streamed out
    on_done: Optional[Any] = None      # stream-close callback (rid, reason)
    submit_t: float = 0.0              # engine-clock time at add_request
    deadline: Optional[float] = None   # absolute end-to-end deadline
    queue_deadline: Optional[float] = None  # absolute admission deadline
    finish_reason: Optional[str] = None
    notified: bool = False             # on_done already fired
    # telemetry: engine-clock times a committed token value first/last
    # became host-visible (None until the first materialisation)
    first_tok_t: Optional[float] = None
    last_tok_t: Optional[float] = None

    @property
    def done(self) -> bool:
        return self.state == DONE

    @property
    def finished(self) -> bool:
        return self.state in TERMINAL

    @property
    def seq(self) -> List[int]:
        """Full token sequence whose KV must be resident to decode."""
        return self.prompt + self.generated


def layer_kinds(cfg: ModelConfig) -> List[LayerKind]:
    """Kinds of the flat layers, period-major: the order of the paged KV
    pool's layer axis and of ``transformer.scan_layer_stack``."""
    return ([cfg.layer_pattern[i] for _ in range(cfg.num_periods)
             for i in range(cfg.period)]
            + [cfg.layer_pattern[i] for i in range(cfg.remainder_layers)])


@jax.jit
def _period_slice(stacked, pi):
    return jax.tree.map(lambda x: x[pi], stacked)


def layer_params(cfg: ModelConfig, params, j: int) -> Dict:
    """Parameters of flat layer ``j``, sliced out of the period stack
    when it is used: the eager paths hold one layer at a time, so the
    weights are never held twice."""
    n_stacked = cfg.num_periods * cfg.period
    if j >= n_stacked:
        return params["rem"][j - n_stacked]
    pi, i = divmod(j, cfg.period)
    return _period_slice(params["blocks"][f"sub{i}"], np.int32(pi))


def _span_segments(path, start: int, end: int) -> List[Tuple[Any, int, int]]:
    """``(node, lo, hi)`` for each node of ``path`` that the span
    ``[start, end)`` covers, ``lo:hi`` in span-local coordinates (where
    a chunk's KV lands and its SSM states are cached)."""
    segments, off = [], 0
    for node in path:
        lo = max(0, off - start)
        hi = min(end, off + node.length) - start
        if hi > lo:
            segments.append((node, lo, hi))
        off += node.length
    return segments


class DecodeEngine:
    def __init__(self, cfg: ModelConfig, params, *,
                 page_size: int = 16, num_pages: int = 4096,
                 backend: str = "codec-pallas",
                 num_lanes: int = 2, max_q: int = 32,
                 max_kv_per_task: int = 2048,
                 replan_interval: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0,
                 prefill_chunk=None, reserve_pages: int = 0,
                 max_running: Optional[int] = None,
                 cascade: bool = False,
                 max_cascade_group: int = 8,
                 fused: bool = False,
                 mesh=None, seq_split_pages: int = 0,
                 replicate: bool = False, calibrate: bool = False,
                 speculative=None, cache=None,
                 faults=None, nan_guard: bool = False,
                 check_every: int = 0, clock=None,
                 max_dispatch_retries: int = 4,
                 telemetry=None):
        assert cfg.encoder_layers == 0, "engine serves decoder-only archs"
        self.cfg = cfg
        self.params = params
        self.backend = backend
        self._backend = registry_mod.get(backend)
        if (cfg.sliding_window and not self._backend.supports_window
                and any(k.mixer == "attn_local"
                        for k in cfg.layer_pattern)):
            raise ValueError(f"backend {backend!r} cannot serve "
                             f"sliding-window layers")
        # ---- SPMD mesh mode (distributed/, DESIGN.md §9) -------------- #
        # mesh != None serves over a (data, model) device mesh: sharded
        # KV pool, per-shard plans, the whole step under shard_map.
        self.mesh = mesh
        if mesh is not None:
            if not fused:
                raise ValueError("mesh serving runs only the fused step; "
                                 "pass fused=True")
            if not (self._backend.jit_safe and self._backend.shardable):
                raise ValueError(
                    f"backend {backend!r} is not shardable; choose one of "
                    f"{registry_mod.names(shardable=True)}")
            D, M = mesh.shape["data"], mesh.shape["model"]
            if D & (D - 1):
                raise ValueError(f"data axis must be a power of two "
                                 f"(POR butterfly), got {D}")
            if M > 1 and (cfg.num_heads % M or cfg.num_kv_heads % M):
                raise ValueError(
                    f"model axis {M} must divide heads "
                    f"({cfg.num_heads} q / {cfg.num_kv_heads} kv)")
        self.page_size = page_size
        self.num_lanes = num_lanes
        self.max_q = max_q
        self.max_kv_per_task = max_kv_per_task
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)

        # ---- fault tolerance (serving/faults.py, DESIGN.md §12) ------- #
        # clock: injectable monotonic time source — deadlines are
        # enforced against it at step boundaries, so tests and the chaos
        # harness drive it deterministically (e.g. one unit per step).
        # The default is the host clock the engine's spans read
        # (telemetry.Span), so Chrome events share their readings.
        self.clock = clock if clock is not None else time.perf_counter
        # ---- telemetry (serving/telemetry.py, DESIGN.md §13) ---------- #
        # telemetry=True builds a default Telemetry; pass an instance to
        # set profile_every / inject a TraceSink.  Span timestamps ride
        # the engine clock above, so fake clocks give deterministic
        # traces.  telemetry=None keeps every hook a no-op.
        if telemetry is True:
            telemetry = telemetry_mod.Telemetry()
        self.telemetry: Optional[telemetry_mod.Telemetry] = \
            telemetry or None
        if self.telemetry is not None:
            self.telemetry.bind_clock(self.clock)
        # prefill's program builds are counted by one listener per process
        telemetry_mod.listen_for_builds()
        self.nan_guard = bool(nan_guard)
        if self.nan_guard and mesh is not None:
            raise ValueError(
                "nan_guard is not supported with mesh serving: the "
                "sharded step fn does not emit per-row finite flags")
        self.check_every = int(check_every)
        self.max_dispatch_retries = int(max_dispatch_retries)
        if faults is not None and not isinstance(faults,
                                                 faults_mod.FaultInjector):
            faults = faults_mod.FaultInjector(faults)
        self.injector: Optional[faults_mod.FaultInjector] = faults
        # admission-shrink rung of the degradation ladder: extra pages
        # the watermark holds back after repeated dispatch OOM
        self._backoff_pages = 0
        # (page, slot) pairs the nan_logits injector poisoned: scrubbed
        # when the target request is quarantined so a future tenant of
        # those pages can never read the NaNs
        self._nan_dirty: List[Tuple[int, int]] = []

        # ---- speculative tree-decoding mode (DESIGN.md §10) ----------- #
        # speculative=True (defaults) or a SpecConfig turns each decode
        # step into a draft-propose / tree-verify / accept-rollback loop:
        # multiple tokens commit per dispatch when the self-drafting
        # proposer guesses right.  Greedy-only, attention-only, and
        # single-device for now (sharded speculation: ROADMAP open item).
        if speculative is True:
            speculative = spec_mod.SpecConfig()
        self.spec: Optional[spec_mod.SpecConfig] = speculative or None
        if self.spec is not None:
            if temperature != 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only: acceptance "
                    "compares draft tokens against the argmax (lossless "
                    "speculative sampling is not implemented)")
            if any(k.mixer == "mamba" for k in cfg.layer_pattern):
                raise ValueError(
                    "speculative decoding needs KV-cache rollback; "
                    "recurrent (Mamba) state cannot be rolled back yet")
            if mesh is not None:
                raise ValueError(
                    "sharded speculation is not implemented "
                    "(ROADMAP open item); drop mesh= or speculative=")
        self.proposer = (spec_mod.NGramProposer(self.spec)
                         if self.spec else None)
        self._drafts: Dict[int, spec_mod.DraftState] = {}
        self._next_virt = -2          # virtual branch-head query ids

        self.layer_kinds = layer_kinds(cfg)
        self.attn_layer_idx = {j: a for a, j in enumerate(
            j for j, k in enumerate(self.layer_kinds)
            if k.mixer in ("attn", "attn_local"))}
        n_attn = len(self.attn_layer_idx)
        if mesh is not None:
            from ..distributed.kv_pool import ShardedKVPool
            self.pool = ShardedKVPool(max(n_attn, 1), num_pages, page_size,
                                      max(cfg.num_kv_heads, 1),
                                      max(cfg.head_dim, 1), mesh=mesh,
                                      seq_split_pages=seq_split_pages)
        else:
            self.pool = PagedKVPool(max(n_attn, 1), num_pages, page_size,
                                    max(cfg.num_kv_heads, 1),
                                    max(cfg.head_dim, 1))
        # ---- replication-aware placement + measured-cost calibration -- #
        # replicate=True lets the sharded epoch copy hot short prefix
        # nodes onto EVERY data shard (extra pages instead of merge
        # wire — CostModel.replicate_gain decides per node); calibrate=
        # True blocks each dispatch to measure it and refits the cost
        # model's hardware coefficients from step_stats at plan epochs.
        self.replicate = bool(replicate) and mesh is not None \
            and mesh.shape["data"] > 1
        self.calibrate = bool(calibrate)
        self._epoch_features: Dict[str, float] = {}
        # per-shard feature vectors of the current epoch (profiled
        # sharded steps attach them for per-shard attribution)
        self._epoch_shard_features: Dict[str, List[float]] = {}
        self.forest = tree_mod.PrefixForest(page_size)
        # splitting a pinned node must extend each waiting holder's pin
        # list over the new lower half (see _on_split_pins)
        self.forest.on_split = self._on_split_pins
        # ---- persistent cross-request prefix cache (serving/cache.py) - #
        # cache=True (default policy) or a CachePolicy keeps finished
        # requests' prefix nodes resident: completed requests *detach*
        # instead of freeing, LRU/TTL eviction bounds residency, and
        # cached nodes are the first reclaim tier under pressure.
        # cache=None (default) preserves the closed-batch behaviour.
        if cache is True:
            cache = CachePolicy()
        self.cache: Optional[PrefixCache] = (
            PrefixCache(self.forest, cache) if cache is not None else None)
        # rolling snapshot so step_stats deltas also cover lookups from
        # eager admissions that happen between steps (add_request)
        self._cache_snap = dict(self.cache.stats) if self.cache else None
        self.requests: Dict[int, Request] = {}
        self._next_rid = 0
        self.cost_model = CostModel(max(cfg.num_heads, 1),
                                    max(cfg.num_kv_heads, 1),
                                    max(cfg.head_dim, 1),
                                    page_size=page_size)
        self.policy = AdmissionPolicy(
            prefill_chunk=prefill_chunk, reserve_pages=reserve_pages,
            max_running=max_running,
            cascade=bool(cascade), max_cascade_group=max_cascade_group,
            draft_reserve_pages=self.spec.max_nodes if self.spec else 0)
        self.admission = AdmissionController(self.policy, self.cost_model,
                                             page_size)
        self._prefilling: List[int] = []   # admitted, prompt not fully prefilled
        # mamba per-request state, keyed by layer index
        self.mamba_state: Dict[int, Any] = {}
        # position the carried mamba state of a PREFILL request is valid at
        self._mamba_pos: Dict[int, int] = {}
        # plans keyed by window size (0 = full attention)
        self._plans: Dict[int, Any] = {}
        # mesh mode: last epoch's ShardedPlan per window (stats/bench)
        self._sharded_plans: Dict[int, Any] = {}
        self._plan_dirty = True
        self._plan_key: Optional[tuple] = None
        self.replan_interval = replan_interval
        self._steps_since_plan = 0
        self.stats = {"steps": 0, "replans": 0, "plan_time": 0.0,
                      "decode_dispatch_time": 0.0,
                      "decode_sync_time": 0.0, "prefill_tokens": 0,
                      "prefill_s": 0.0, "prefill_build_s": 0.0,
                      "prefill_builds": 0, "prefill_jit_chunks": 0,
                      "prefill_eager_chunks": 0, "prefill_signatures": 0,
                      "admitted": 0, "preempted": 0, "reclaimed": 0,
                      "recompute_tokens": 0, "prefill_chunks": 0,
                      "prefill_stalls": 0, "fused_calls": 0,
                      "token_flushes": 0, "spec_steps": 0,
                      "spec_proposed": 0, "spec_accepted": 0,
                      "spec_draft_stalls": 0, "calibrations": 0,
                      "replica_promotions": 0, "replica_demotions": 0,
                      "cancelled": 0, "timed_out": 0, "failed": 0,
                      "callback_errors": 0, "faults_injected": 0,
                      "dispatch_failures": 0, "dispatch_recoveries": 0,
                      "nan_rows": 0, "invariant_checks": 0,
                      "cascade_groups": 0, "cascade_shared_tokens": 0,
                      "cascade_suffix_tokens": 0, "cascade_batches": 0}
        self.step_stats: List[Dict] = []
        self._decode_timing: Dict[str, float] = {}

        # ---- fused single-dispatch decode (serving/step_fn.py) -------- #
        # requested via ``fused=True``; active only for backends that
        # satisfy the registry's jit-safe partials contract (``ref``
        # falls back to the eager per-layer path).
        self.fused = bool(fused) and self._backend.jit_safe
        self._mamba_layer_js = [j for j, k in enumerate(self.layer_kinds)
                                if k.mixer == "mamba"]
        self._step_fn = None
        self._spec_step_fn = None
        self._replicated_sharding = None
        if self.fused and self.spec is not None:
            # speculative mode replaces the per-token decode dispatch
            # with the fused multi-query verification dispatch
            self._spec_step_fn = step_fn_mod.make_spec_step_fn(
                cfg, self._backend, tuple(self._windows()))
        elif self.fused and mesh is not None:
            from ..distributed import step_fn as sharded_step_fn_mod
            self._step_fn = sharded_step_fn_mod.make_sharded_step_fn(
                cfg, self._backend, tuple(self._windows()), temperature,
                mesh)
            # commit host-built step inputs to the replicated sharding so
            # the first dispatch and steady-state dispatches share one jit
            # signature (uncommitted vs replicated would compile twice)
            self._replicated_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            self.key = jax.device_put(self.key, self._replicated_sharding)
            # weights live replicated on every device once; left
            # uncommitted, each dispatch would copy them to the mesh
            self.params = jax.device_put(params, self._replicated_sharding)
        elif self.fused:
            self._step_fn = step_fn_mod.make_step_fn(
                cfg, self._backend, tuple(self._windows()), temperature)
        # ---- prefill program (step_fn.make_prefill_fn) ---------------- #
        # an attention-only stack on the single-device pool prefills each
        # chunk as one jitted, donated dispatch per (T_pad, P_pad) shape
        # bucket; recurrent (Mamba) mixers and the mesh pool keep the
        # eager per-layer chunk (_prefill_eager)
        self._prefill_fn = None
        if mesh is None and all(k.mixer in ("attn", "attn_local")
                                for k in self.layer_kinds):
            self._prefill_fn = step_fn_mod.make_prefill_fn(cfg, temperature)
        # distinct prefill program shapes seen (bounds its jit cache)
        self.prefill_signatures: set = set()
        # epoch state: valid between plan rebuilds
        self._fused_rows: Optional[List[int]] = None
        self._fused_base: Optional[step_fn_mod.StepBase] = None
        self._fused_prepared: Optional[tuple] = None
        self._fused_bucket = 0
        self._fused_delta = 0
        self._mamba_carry = None          # (conv_all, ssm_all) device stacks
        # async token plumbing
        self._deferred: List[_Deferred] = []
        self._pending_ref: Dict[int, Tuple[_Deferred, int]] = {}
        self._flushed_since_dispatch = True
        self._last_out: Optional[Tuple[List[int], Any]] = None
        # distinct fused shape signatures seen (compile-cache regression
        # tests bound the jit cache size by this set's size)
        self.bucket_signatures: set = set()

    # ------------------------------------------------------------------ #
    # request admission (admit phase) + chunked prefill (prefill phase)
    # ------------------------------------------------------------------ #
    def add_request(self, prompt: List[int], max_new: int = 16,
                    on_token=None, on_done=None,
                    deadline_s: Optional[float] = None,
                    max_queue_s: Optional[float] = None) -> int:
        """Enqueue a request; admits (and prefills) eagerly when memory
        allows, so under no pressure this behaves like immediate prefill.

        ``on_token(rid, token)`` streams each generated token as soon as
        its host value exists (immediately on the eager path; at sync
        boundaries on the fused async path).  ``on_done(rid, reason)``
        closes the stream exactly once with a terminal reason (``done``,
        ``cancelled``, ``deadline``, ``queue_timeout``, or a failure
        reason such as ``nan_logits`` / ``callback_error``).

        ``deadline_s`` bounds the request END TO END (queueing included)
        and ``max_queue_s`` bounds time spent WAITING; both are relative
        to now on the engine clock and enforced at step boundaries — an
        expired request transitions to ``TIMED_OUT`` with its KV
        released.  A deadline also promotes the request in the waiting
        queue (EDF ordering, ``core.scheduler.AdmissionController``).
        """
        prompt = list(prompt)
        if not prompt:
            raise ValueError("prompt must contain at least one token")
        if max_new <= 0:
            raise ValueError(f"max_new must be positive, got {max_new}")
        arr = np.asarray(prompt)
        if not np.issubdtype(arr.dtype, np.integer):
            raise ValueError(
                f"prompt must be integer token ids, got dtype {arr.dtype}")
        lo, hi = int(arr.min()), int(arr.max())
        if lo < 0 or hi >= self.cfg.vocab_size:
            raise ValueError(
                f"prompt token id {lo if lo < 0 else hi} outside the "
                f"vocabulary [0, {self.cfg.vocab_size})")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be positive, got {deadline_s}")
        if max_queue_s is not None and max_queue_s <= 0:
            raise ValueError(
                f"max_queue_s must be positive, got {max_queue_s}")
        # only an *unservable* prompt is an error: whole-prompt prefill
        # needs every page at once, chunked prefill only one chunk + the
        # tail it grows into (larger prompts just wait in the queue)
        need = self.policy.min_working_pages(len(prompt), self.page_size)
        if need > self.pool.num_pages:
            raise MemoryError(
                f"prompt working set needs {need} KV pages but the pool "
                f"holds only {self.pool.num_pages}: it can never be "
                f"admitted")
        rid = self._next_rid
        self._next_rid += 1
        now = self.clock()
        req = Request(rid, prompt, max_new=max_new,
                      on_token=on_token, on_done=on_done, submit_t=now)
        if deadline_s is not None:
            req.deadline = now + float(deadline_s)
        if max_queue_s is not None:
            req.queue_deadline = now + float(max_queue_s)
        self.requests[rid] = req
        if self.telemetry is not None:
            self.telemetry.metrics["requests_submitted"].inc()
            self.telemetry.begin("queued", track=rid,
                                 args={"prompt_tokens": len(prompt),
                                       "max_new": max_new})
        edf = [d for d in (req.deadline, req.queue_deadline)
               if d is not None]
        self.admission.push(rid, deadline=min(edf) if edf else None)
        self._admit_phase()
        return rid

    def has_work(self) -> bool:
        return any(q.state in (WAITING, PREFILL, RUNNING)
                   for q in self.requests.values())

    def _live(self) -> List[int]:
        return [r for r in sorted(self.requests)
                if self.requests[r].state in (PREFILL, RUNNING)]

    def _active_rows(self) -> List[int]:
        return [r for r in sorted(self.requests)
                if self.requests[r].state == RUNNING]

    def _has_pages_for(self, req: Request) -> bool:
        seq = req.seq
        matched = self.forest.match_len(np.asarray(seq, np.int32))
        need = (-(-max(len(seq), 1) // self.page_size)
                - matched // self.page_size)
        # the draft reserve scales with *currently running* requests so an
        # idle engine always admits its head-of-line request (a reserve
        # counting the candidate itself could starve admission forever on
        # a pool barely larger than one working set).  _backoff_pages is
        # the degradation ladder's admission-shrink rung: after repeated
        # dispatch OOM the watermark rises so retries run with headroom.
        reserve = (self.policy.admission_reserve(len(self._active_rows()))
                   + self._backoff_pages)
        return self.pool.num_free - reserve >= need

    def _admit_phase(self) -> None:
        """Admission + chunked-prefill phase.

        Continues admitted prefills first, then admits waiting requests
        FCFS within the page watermark (reclaiming finished-request KV if
        needed) and the per-step cost-model prefill budget.  With
        ``cascade=True`` prefilling requests that share an unfilled
        forest node advance as one cascade group — the shared span is
        computed once, the suffix chunks batch into one dispatch — and
        admitting a head-of-line request pulls its cascade partners out
        of the wait queue so the group prefills together (DESIGN.md §14).
        """
        running_ctx = [self.forest.context_len(r)
                       for r in self._active_rows()]
        budget = self.admission.prefill_budget(running_ctx)
        # 1. advance chunked prefills already admitted
        spent = self._advance_prefills(budget)
        # 2. admit from the queue (FCFS; head-of-line blocks)
        while len(self.admission):
            if budget is not None and spent >= budget:
                return
            if (self.policy.max_running is not None
                    and len(self._live()) >= self.policy.max_running):
                return                      # capacity cap, not memory
            head = self.requests[self.admission.peek()]
            need_min = self.policy.min_working_pages(len(head.seq),
                                                     self.page_size)
            if need_min > self.pool.num_pages:
                raise MemoryError(
                    f"request {head.rid} needs a {need_min}-page working "
                    f"set but the pool holds only {self.pool.num_pages}")
            while not self._has_pages_for(head):
                if not self._reclaim_one(set(), allow_preempt=False):
                    return                  # no free memory: keep waiting
            # admission boundary: radix INSERTION compares token values,
            # so in-flight device tokens must land before _admit.  The
            # space probe above tolerates placeholders (-1 never equals
            # a real token, so match_len only under-matches and the page
            # need is over-estimated) — a head-of-line request blocked
            # on memory does NOT cost the fused path a sync per step.
            self.flush_tokens()
            self.admission.pop()
            with telemetry_mod.Span("codec.admit"):
                self._admit(head)
                group = [head.rid]
                if self.policy.cascade:
                    group += self._co_admit_partners(head)
                spent += self._prefill_group(
                    group, None if budget is None else budget - spent)

    def _advance_prefills(self, budget: Optional[int]) -> int:
        """Advance every admitted-but-unfinished prefill by one chunk."""
        spent = 0
        if not self.policy.cascade:
            for rid in list(self._prefilling):
                if budget is not None and spent >= budget:
                    return spent
                req = self.requests[rid]
                if req.state != PREFILL:   # preempted by an earlier prefill
                    continue
                spent += self._prefill_step(
                    req, None if budget is None else budget - spent)
            return spent
        # cascade mode: regroup every step — membership is derived from
        # the forest (first unfilled node on each path), so preemption,
        # node splits and members completing at different times all fall
        # out of the grouping instead of needing group-object surgery
        for group in self._prefill_groups():
            if budget is not None and spent >= budget:
                return spent
            spent += self._prefill_group(
                group, None if budget is None else budget - spent)
        return spent

    def _co_admit_partners(self, head: Request) -> List[int]:
        """Pull the head's cascade partners out of the wait queue.

        A partner is a waiting request whose prompt's deepest shared
        forest node (``tree.match_path``) lies on the head's freshly
        inserted path: prefilling it now means the shared span is
        computed once for the whole group instead of once per request.
        Co-admission is opportunistic — the page probe and the
        ``max_running`` cap still apply, and a partner failing either
        simply keeps its place in the queue.
        """
        anchor = {n.id for n in self.forest.path(head.rid) if n.length}
        if not anchor:
            return []
        ps = self.page_size

        def key_of(rid: int) -> Optional[int]:
            nid, matched = self.forest.match_path(
                np.asarray(self.requests[rid].seq, np.int32))
            # < one page shared: insertion would not even split a node,
            # so there is no shared span to cascade over
            return nid if matched >= ps else None

        admitted: List[int] = []
        limit = self.policy.max_cascade_group - 1
        for rid in self.admission.cascade_partners(anchor, key_of, limit):
            if (self.policy.max_running is not None
                    and len(self._live()) >= self.policy.max_running):
                break
            part = self.requests[rid]
            if not self._has_pages_for(part):
                continue
            self.admission.remove(rid)
            self._admit(part)
            admitted.append(rid)
        return admitted

    def _cascade_key(self, rid: int) -> Optional[int]:
        """Id of the first not-fully-filled node on the request's path.

        Prefilling requests that map to the same key are about to compute
        the same node's KV — they form one cascade group and share that
        span's forward pass (``None`` = nothing left to fill).
        """
        for node in self.forest.path(rid):
            if node.length == 0:
                continue
            if min(node.meta.get("filled", 0), node.length) < node.length:
                return node.id
        return None

    def _prefill_groups(self) -> List[List[int]]:
        """Partition ``_prefilling`` into cascade groups (order kept)."""
        groups: List[List[int]] = []
        by_key: Dict[int, int] = {}
        for rid in list(self._prefilling):
            if self.requests[rid].state != PREFILL:
                continue
            key = self._cascade_key(rid)
            if key is not None and key in by_key:
                groups[by_key[key]].append(rid)
            else:
                if key is not None:
                    by_key[key] = len(groups)
                groups.append([rid])
        return groups

    def _prefill_group(self, group: List[int],
                       budget: Optional[int]) -> int:
        group = [r for r in group if self.requests[r].state == PREFILL]
        if not group:
            return 0
        if len(group) == 1:
            return self._prefill_step(self.requests[group[0]], budget)
        return self._cascade_prefill_step(group, budget)

    def _filled_front(self, rid: int) -> int:
        """Contiguous filled-KV front along the request's path."""
        filled = 0
        for node in self.forest.path(rid):
            f = min(node.meta.get("filled", 0), node.length)
            filled += f
            if f < node.length:
                break
        return filled

    def _shared_frontier(self, group: List[int]) -> int:
        """Absolute end position of the deepest node common to every
        member's path — the span whose compute the group shares."""
        paths = [self.forest.path(r) for r in group]
        end = 0
        for nodes in zip(*paths):
            nid = nodes[0].id
            if any(n.id != nid for n in nodes[1:]):
                break
            end = nodes[0].end_pos
        return end

    def _cascade_prefill_step(self, group: List[int],
                              budget: Optional[int]) -> int:
        """Advance a cascade group by one chunk (DESIGN.md §14).

        Phase A computes the group's shared uncached span exactly once:
        one forward over the common path (through the lead member), KV
        written into the shared nodes' pages and SSM boundary states
        cached in ``node.meta["ssm"]`` exactly as the sequential path
        does — then hands every sibling the carried mid-node SSM state so
        all of them resume identically from the chunk boundary.  Phase B
        batches the per-request suffix chunks into one padded dispatch;
        recurrent (Mamba) suffixes fall back to the per-request path
        (the shared phase still cascades), and members whose next
        unfilled node is shared with another member recurse as a deeper
        cascade subgroup.  A member stalling on pages is skipped while
        its siblings proceed; a stall on the *shared* span stalls the
        group (the span is on every member's path).
        """
        spent = 0
        alive = [r for r in group if self.requests[r].state == PREFILL]
        if len(alive) < 2:
            return self._prefill_group(alive, budget)
        self.stats["cascade_groups"] += 1

        # ---- phase A: shared uncached span, computed once ------------- #
        lead = self.requests[alive[0]]
        frontier = self._shared_frontier(alive)
        if self._filled_front(lead.rid) < frontier:
            # the shared chunk belongs to every member's prefill span,
            # not just the lead's (§13 nesting)
            n = self._prefill_step(lead, budget, stop_at=frontier,
                                   shared_with=alive[1:])
            spent += n
            if n:
                self.stats["cascade_shared_tokens"] += n * (len(alive) - 1)
            # hand each sibling the carried mid-node SSM state so hybrid
            # archs resume from the cascaded chunk boundary instead of
            # recomputing from the last node-aligned ``meta["ssm"]``
            pos = self._mamba_pos.get(lead.rid)
            if pos is not None and pos <= frontier:
                for rid in alive[1:]:
                    self._mamba_pos[rid] = pos
                    for st in self.mamba_state.values():
                        if lead.rid in st:
                            st[rid] = st[lead.rid]
            if n == 0:
                self.stats["prefill_stalls"] += len(alive) - 1
                return spent       # shared-span page stall: group waits
            if budget is not None and spent >= budget:
                return spent
            if self._filled_front(lead.rid) < frontier:
                return spent       # chunk ended mid-shared-span

        # ---- phase B: per-request suffix chunks, one dispatch --------- #
        alive = [r for r in alive if self.requests[r].state == PREFILL]
        has_mamba = any(k.mixer == "mamba" for k in self.layer_kinds)
        by_key: Dict[int, List[int]] = {}
        for rid in alive:
            key = self._cascade_key(rid)
            by_key.setdefault(key if key is not None else ~rid,
                              []).append(rid)
        batch: List[Tuple[Request, int, int]] = []
        for key, rids in by_key.items():
            if budget is not None and spent >= budget:
                break
            left = None if budget is None else budget - spent
            if len(rids) > 1:
                # a deeper node shared by a strict subset of the group:
                # cascade it as its own subgroup (phase A recursion)
                spent += self._cascade_prefill_step(rids, left)
                continue
            req = self.requests[rids[0]]
            total = len(req.seq)
            start = self._filled_front(req.rid)
            if has_mamba or start >= total:
                # recurrent suffix / fully-cached prompt: per-request
                # path (promotion + final-logit recompute live there)
                spent += self._prefill_step(req, left)
                continue
            end = total if left is None else min(total, start + left)
            if not self._ensure_pages_upto(req.rid, end):
                self.stats["prefill_stalls"] += 1
                continue           # this member stalls; siblings proceed
            batch.append((req, start, end))
            spent += end - start
        if len(batch) == 1:
            req, start, end = batch[0]
            self._prefill_step(req, end - start)
        elif batch:
            self._batched_suffix_prefill(batch)
        return spent

    def _batched_suffix_prefill(
            self, batch: List[Tuple["Request", int, int]]) -> int:
        """One padded dispatch over several requests' suffix chunks.

        Cascade phase B: each row is a ``(request, start, end)`` span
        whose pages are already ensured and whose KV front is filled up
        to ``start``.  Rows pad to pow2 buckets (``core.plan.bucket_pow2``
        conventions — query length, KV length and batch); padded query
        slots carry position -1 (``L.mha`` masks them to a finite
        uniform), padded KV slots are masked via ``kv_valid``.  Per-row
        KV writes, sampling order and telemetry spans match the
        sequential per-request path.
        """
        cfg = self.cfg
        tm = self.telemetry
        B = len(batch)
        Tn = [end - start for _, start, end in batch]
        done = sum(Tn)
        finished: List[Request] = []
        with telemetry_mod.PrefillSpan(
                self.stats, tm, track=batch[0][0].rid,
                args={"tokens": Tn[0], "batched": True}) as sp:
            T_pad = plan_mod.bucket_pow2(max(Tn))
            S_pad = plan_mod.bucket_pow2(max(end for _, _, end in batch))
            B_pad = plan_mod.bucket_pow2(B)

            tok = np.zeros((B_pad, T_pad), np.int32)
            qpos = np.full((B_pad, T_pad), -1, np.int32)
            kv_valid = np.zeros((B_pad, S_pad), bool)
            for i, (req, start, end) in enumerate(batch):
                tok[i, :Tn[i]] = req.seq[start:end]
                qpos[i, :Tn[i]] = start + np.arange(Tn[i])
                kv_valid[i, :end] = True
            kv_pos = np.broadcast_to(np.arange(S_pad, dtype=np.int32),
                                     (B_pad, S_pad))
            qpos_j = jnp.asarray(qpos)

            paths = {req.rid: self.forest.path(req.rid)
                     for req, _, _ in batch}
            segments = {req.rid: _span_segments(paths[req.rid], start, end)
                        for req, start, end in batch}

            x = T._embed(self.params, cfg, jnp.asarray(tok), qpos_j)
            new_kv_writes = []   # (layer_attn, k (B_pad,T_pad,kv,hd), v)
            for j, kind in enumerate(self.layer_kinds):
                p = layer_params(cfg, self.params, j)
                h = L.apply_norm(p["ln"], x, cfg)
                la = self.attn_layer_idx[j]
                window = (cfg.sliding_window if kind.mixer == "attn_local"
                          else 0)
                q, k_new, v_new = L.attn_project(p["attn"], cfg, h, qpos_j)
                with telemetry_mod.Span("codec.prefill.gather"):
                    k_rows, v_rows = [], []
                    for i, (req, start, end) in enumerate(batch):
                        pk, pv = self._gather_prefix_upto(
                            la, paths[req.rid], start)
                        kr = jnp.concatenate([pk.astype(k_new.dtype),
                                              k_new[i, :Tn[i]]], 0)
                        vr = jnp.concatenate([pv.astype(v_new.dtype),
                                              v_new[i, :Tn[i]]], 0)
                        pad = S_pad - kr.shape[0]
                        if pad:
                            kr = jnp.pad(kr, ((0, pad), (0, 0), (0, 0)))
                            vr = jnp.pad(vr, ((0, pad), (0, 0), (0, 0)))
                        k_rows.append(kr)
                        v_rows.append(vr)
                    k_all = jnp.stack(k_rows, 0)
                    v_all = jnp.stack(v_rows, 0)
                    if B_pad > B:
                        zpad = ((0, B_pad - B), (0, 0), (0, 0), (0, 0))
                        k_all = jnp.pad(k_all, zpad)
                        v_all = jnp.pad(v_all, zpad)
                with telemetry_mod.Span("codec.prefill.attn"):
                    o = L.mha(q, k_all, v_all, causal=True, window=window,
                              softcap=cfg.attn_logit_softcap,
                              q_positions=qpos_j,
                              kv_positions=jnp.asarray(kv_pos),
                              kv_valid=jnp.asarray(kv_valid))
                y = L.dense(p["attn"]["wo"],
                            o.reshape(B_pad, T_pad,
                                      cfg.num_heads * cfg.head_dim))
                new_kv_writes.append((la, k_new, v_new))
                x = x + y
                x, _ = L.apply_ffn_block(p, cfg, kind.ffn, x)

            # write each row's new KV into its own nodes' unfilled slots
            with telemetry_mod.Span("codec.prefill.kv_write"):
                pages, offs, rows_b, rows_t = [], [], [], []
                for i, (req, start, end) in enumerate(batch):
                    rows, pg, of = self._new_kv_slots(segments[req.rid],
                                                      start)
                    pages += pg
                    offs += of
                    rows_t += rows
                    rows_b += [i] * len(rows)
                if pages:
                    bi = jnp.asarray(np.asarray(rows_b))
                    ti = jnp.asarray(np.asarray(rows_t))
                    for la, k_new, v_new in new_kv_writes:
                        self.pool.write_tokens(la, np.asarray(pages),
                                               np.asarray(offs),
                                               k_new[bi, ti], v_new[bi, ti])

            self.stats["prefill_tokens"] += done
            self.stats["cascade_suffix_tokens"] += done
            self.stats["cascade_batches"] += 1
            logits_all = None
            for i, (req, start, end) in enumerate(batch):
                self.stats["recompute_tokens"] += max(
                    0, min(end, req.computed_hwm) - start)
                req.computed_hwm = max(req.computed_hwm, end)
                if end < len(req.seq):
                    self.stats["prefill_chunks"] += 1
                    continue
                if req.pending is None:
                    with telemetry_mod.Span("codec.prefill.sample"):
                        if logits_all is None:
                            logits_all = T._unembed(self.params, cfg, x)
                        logits = logits_all[i, Tn[i] - 1]
                        self.key, sk = jax.random.split(self.key)
                        req.pending = int(sampler.sample(
                            logits[None], sk, self.temperature)[0])
                finished.append(req)
        if tm is not None:
            # the span recorded row 0's chunk; the other rows' chunks
            # share its readings
            for i, (req, _, _) in enumerate(batch[1:], 1):
                tm.complete("prefill_chunk", sp.c0, sp.c1, track=req.rid,
                            args={"tokens": Tn[i], "batched": True})
            tm.observe("prefill_chunk_s", sp.c1 - sp.c0)
        # promote after the chunk spans close: each request's "prefill"
        # span ends at promotion and must contain its chunks
        for req in finished:
            self._promote(req)
        return done

    def _admit(self, req: Request) -> None:
        """(Re-)insert the request's sequence into the forest and release
        the pins it held while waiting (its path now keeps those nodes
        alive by membership)."""
        seq = np.asarray(req.seq, np.int32)
        if (self.cache is not None and req.preemptions == 0
                and not req.generated):
            # first admission only: a preemption resume would count its
            # own pinned prefix as a "hit" and inflate the rate
            self.cache.record_lookup(self.forest.match_len(seq), len(seq))
        self.forest.insert_tokens(req.rid, seq)
        if self.cache is not None:
            for node in self.forest.path(req.rid):
                self.cache.stamp(node)
        for nid in req.pinned:
            node = self.forest.nodes.get(nid)
            if node is not None:
                node.meta["pins"] = node.meta.get("pins", 0) - 1
                self._maybe_free_node(node)
        req.pinned = []
        req.state = PREFILL
        self._prefilling.append(req.rid)
        self.stats["admitted"] += 1
        if self.telemetry is not None:
            if req.preemptions == 0 and not req.generated:
                self.telemetry.observe("queue_wait_s",
                                       self.clock() - req.submit_t)
            self.telemetry.end(track=req.rid)          # "queued"
            self.telemetry.begin("prefill", track=req.rid)

    # ------------------------------------------------------------------ #
    # async-token sync (fused path)
    # ------------------------------------------------------------------ #
    def flush_tokens(self) -> None:
        """Materialise every deferred device token on the host.

        The fused decode path appends sampled tokens to the forest and to
        ``Request.generated`` as placeholders while the device arrays are
        still in flight; this is the single blocking host⇄device sync
        point, invoked only at plan-rebuild / admission / eviction /
        completion boundaries (a no-op otherwise — the eager path never
        defers).
        """
        if not self._deferred and not self._pending_ref:
            return
        tm = self.telemetry
        with telemetry_mod.Span("codec.flush", tm, "flush") as sp:
            landed, poisoned = self._land_deferred()
            sp.args = {"tokens": len(landed)}
        elapsed = sp.seconds
        self.stats["token_flushes"] += 1
        self.stats["decode_sync_time"] += elapsed
        # the sync wait is attributed to the step in which the flush
        # actually ran, under its OWN key — it must never pollute that
        # step's dispatch/compute split (async flushing defers syncs to
        # arbitrary later steps; see step_stats "flush_time")
        self._decode_timing["flush_time"] = \
            self._decode_timing.get("flush_time", 0.0) + elapsed
        if tm is not None:
            for rid in landed:
                self._note_token(self.requests[rid])
            tm.observe("flush_s", elapsed)
        for rid, cut in poisoned.items():
            req = self.requests.get(rid)
            if req is None:
                continue
            del req.generated[cut:]       # placeholders only (never -1
            self.stats["nan_rows"] += 1   # streamed, so emitted <= cut)
            self._fail_request(rid, "nan_logits", flush=False)

    def _land_deferred(self) -> Tuple[Set[int], Dict[int, int]]:
        """Write every deferred device token's value into the streams and
        the forest; returns the requests whose tokens landed and, under
        the NaN guard, each poisoned request's first bad index."""
        vals = {id(e): np.asarray(e.tokens) for e in self._deferred}
        # NaN guard: a dispatch whose row_ok flag is False produced
        # non-finite logits for that row — every token of that request
        # from the first poisoned index on is garbage.  Quarantine the
        # request (FAILED) without touching the other rows.
        poisoned: Dict[int, int] = {}     # rid -> earliest bad gen index
        if self.nan_guard:
            for e in self._deferred:
                if e.ok is None:
                    continue
                okv = np.asarray(e.ok)
                for rid, row, gen_idx, _nid, _tid in e.patches:
                    if not bool(okv[row]) and gen_idx < poisoned.get(
                            rid, gen_idx + 1):
                        poisoned[rid] = gen_idx
            for rid, (e, row) in self._pending_ref.items():
                if (e.ok is not None and not bool(np.asarray(e.ok)[row])
                        and rid not in poisoned):
                    req = self.requests.get(rid)
                    if req is not None:   # sampled, never appended
                        poisoned[rid] = len(req.generated)
        landed: Set[int] = set()
        for e in self._deferred:
            v = vals[id(e)]
            for rid, row, gen_idx, node_id, tok_idx in e.patches:
                if gen_idx >= poisoned.get(rid, gen_idx + 1):
                    continue              # untrusted suffix: never lands
                tok = int(v[row])
                req = self.requests.get(rid)
                if req is not None and gen_idx < len(req.generated):
                    req.generated[gen_idx] = tok
                    landed.add(rid)
                node = self.forest.nodes.get(node_id)
                if (node is not None and node.tokens is not None
                        and tok_idx < len(node.tokens)):
                    node.tokens[tok_idx] = tok
        # sampled-but-not-yet-appended tokens become host ``pending``s
        for rid, (e, row) in self._pending_ref.items():
            req = self.requests.get(rid)
            if (req is not None and req.pending is PENDING_DEVICE
                    and rid not in poisoned):
                req.pending = int(vals[id(e)][row])
        self._deferred = []
        self._pending_ref = {}
        self._flushed_since_dispatch = True
        return landed, poisoned

    # ------------------------------------------------------------------ #
    # eviction (evict phase) / reclamation
    # ------------------------------------------------------------------ #
    def _maybe_free_node(self, node, force: bool = False) -> None:
        """Free a node once nothing references it: no requests pass
        through it, it has no children, and no evicted request pins it.

        With the prefix cache enabled, page-backed nodes are *retained*
        instead (they become cache content, reclaimed by TTL/LRU sweep
        or the pressure tier); ``force=True`` bypasses retention for
        callers that must actually free (pressure reclaim)."""
        if node.id == tree_mod.ROOT_ID or node.id not in self.forest.nodes:
            return
        if node.requests or node.children or node.meta.get("pins", 0) > 0:
            return
        if (not force and self.cache is not None
                and self.cache.retainable(node)):
            if "touch" not in node.meta:
                self.cache.stamp(node)
            return
        self._release_node_pages(node)
        parent = self.forest.nodes[node.parent]
        parent.children.remove(node.id)
        del self.forest.nodes[node.id]
        self._maybe_free_node(parent)

    def _release_kv(self, rid: int, force_leaf: bool = False) -> None:
        """Drop a request's forest footprint (finished or released).

        ``force_leaf=True`` (FAILED requests) bypasses cache retention
        for the request's PRIVATE leaf: its tail KV may be poisoned
        (NaN quarantine) or half-written, so it must never be served to
        a future prefix match.  Shared ancestors hold prompt KV written
        by prefill and stay retainable."""
        self._rollback_drafts(rid)
        leaf_id = self.forest.leaf_of.get(rid)
        for node in reversed(self.forest.path(rid)):
            if node.id not in self.forest.nodes:
                continue
            node.requests.remove(rid)
            self._maybe_free_node(node,
                                  force=force_leaf and node.id == leaf_id)
        del self.forest.leaf_of[rid]
        for st in self.mamba_state.values():
            st.pop(rid, None)
        self._mamba_pos.pop(rid, None)

    def _preempt(self, rid: int) -> None:
        """Evict a live request: release its non-shared pages, pin the
        shared prefix nodes it leaves behind, and requeue it (front) to be
        re-prefilled from the radix-cached prefix."""
        # re-prefill recomputes from token values; sync any deferred ones
        self.flush_tokens()
        # a victim evicted mid-speculation sheds its draft tree first:
        # draft nodes/virtual queries would otherwise keep its leaf (and
        # every ancestor) alive and leak the draft pages
        self._rollback_drafts(rid)
        req = self.requests[rid]
        assert req.state in (PREFILL, RUNNING), req.state
        if len(req.generated) >= req.max_new:
            # generation already complete (evicted between its final append
            # and the done transition): nothing to resume, just drop the KV
            self._release_kv(rid)
            if rid in self._prefilling:
                self._prefilling.remove(rid)
            req.state = DONE
            req.kv_freed = True
            self.stats["reclaimed"] += 1
            return
        pinned = []
        for node in reversed(self.forest.path(rid)):
            if node.id not in self.forest.nodes:
                continue
            node.requests.remove(rid)
            if (node.requests or node.children
                    or node.meta.get("pins", 0) > 0):
                node.meta["pins"] = node.meta.get("pins", 0) + 1
                pinned.append(node.id)
            else:
                self._release_node_pages(node)
                parent = self.forest.nodes[node.parent]
                parent.children.remove(node.id)
                del self.forest.nodes[node.id]
        del self.forest.leaf_of[rid]
        for st in self.mamba_state.values():
            st.pop(rid, None)
        self._mamba_pos.pop(rid, None)
        if rid in self._prefilling:
            self._prefilling.remove(rid)
        req.pinned = pinned
        req.state = WAITING
        req.preemptions += 1
        self.admission.requeue(rid)
        self.stats["preempted"] += 1
        if self.telemetry is not None:
            self.telemetry.end_all(rid)       # prefill/decode span
            self.telemetry.instant("evict", track=rid,
                                   args={"pinned_nodes": len(pinned)})
            self.telemetry.begin("queued", track=rid)

    def _reclaimable_pages(self, rid: int) -> int:
        """Pages that preempting ``rid`` would free (its non-shared nodes)."""
        n = 0
        freeable: Set[int] = set()
        for node in reversed(self.forest.path(rid)):
            # virtual branch-head queries (< 0) and draft children belong
            # to a live draft tree; preemption rolls the tree back first,
            # so they must not disqualify the victim (the estimate stays
            # conservative: draft pages themselves are not counted)
            others = [r for r in node.requests if r != rid and r >= 0]
            kids = {c for c in set(node.children) - freeable
                    if not self.forest.nodes[c].meta.get("draft")}
            if others or kids or node.meta.get("pins", 0) > 0:
                continue
            freeable.add(node.id)
            n += self._node_total_pages(node)
        return n

    def _reclaim_one(self, exclude: Set[int],
                     allow_preempt: bool = True) -> bool:
        """Free some pages, cheapest first: (0) evict cached (request-
        less, unpinned) prefix nodes LRU-first, (1) finished-request KV,
        (2) orphaned pinned nodes, (3) preempt the live victim with the
        fewest generated tokens (ties: latest arrival)."""
        if self.cache is not None and self._evict_cached(1) > 0:
            self.stats["reclaimed"] += 1
            return True
        for rid in sorted(self.requests):
            q = self.requests[rid]
            complete = (q.state == DONE
                        or (q.state == RUNNING
                            and len(q.generated) >= q.max_new))
            if (complete and not q.kv_freed and rid not in exclude
                    and rid in self.forest.leaf_of):
                self._release_kv(rid)
                q.state = DONE
                q.kv_freed = True
                self.stats["reclaimed"] += 1
                return True
        for rid in sorted(self.requests):
            q = self.requests[rid]
            if q.state != WAITING or not q.pinned:
                continue
            for nid in list(q.pinned):
                node = self.forest.nodes.get(nid)
                if node is None:
                    q.pinned.remove(nid)
                    continue
                if not node.requests and not node.children:
                    # drop this waiter's pin; the node frees once the last
                    # pin goes (multiply-pinned nodes shed one pin per
                    # holder until the final drop releases the pages)
                    q.pinned.remove(nid)
                    node.meta["pins"] = node.meta.get("pins", 0) - 1
                    self._maybe_free_node(node, force=True)
                    if nid not in self.forest.nodes:
                        self.stats["reclaimed"] += 1
                        return True
        # demote a replicated node (widest first): frees (D-1)/D of its
        # pages without touching any request — always cheaper than
        # preemption, and the plan rebuild re-derives the merge mask
        repl = [n for n in self.forest.nodes.values()
                if "replicas" in n.meta]
        if repl:
            self._demote_replicas(max(repl, key=lambda n: len(n.page_ids)))
            self.stats["reclaimed"] += 1
            return True
        if not allow_preempt:
            return False
        victims = [r for r in sorted(self.requests)
                   if self.requests[r].state in (PREFILL, RUNNING)
                   and r not in exclude
                   and self._reclaimable_pages(r) > 0]
        if not victims:
            return False
        victim = min(victims,
                     key=lambda r: (len(self.requests[r].generated), -r))
        self._preempt(victim)
        return True

    # ------------------------------------------------------------------ #
    # persistent cross-request prefix cache (serving/cache.py)
    # ------------------------------------------------------------------ #
    def _on_split_pins(self, upper, lower) -> None:
        """Forest split observer: ``tree._split`` copies the pin
        refcount to the lower half; the per-request pin *lists* must
        follow, or un-pinning at re-admission would strand the lower
        half pinned forever."""
        # a replicated node splits every replica run at the same page
        # boundary (``tree._split`` already cut ``page_ids``, which hold
        # the primary's rows — the other shards' runs must follow, or
        # the lower half would alias the upper's replica pages)
        reps = upper.meta.get("replicas")
        if reps is not None:
            cut = len(upper.page_ids)
            prim = upper.meta["replica_primary"]
            lower.meta["replicas"] = {s: lst[cut:]
                                      for s, lst in reps.items()}
            upper.meta["replicas"] = {s: lst[:cut]
                                      for s, lst in reps.items()}
            lower.meta["replica_primary"] = prim
            upper.page_ids = list(upper.meta["replicas"][prim])
            lower.page_ids = list(lower.meta["replicas"][prim])
        if upper.meta.get("pins", 0) <= 0:
            return
        for req in self.requests.values():
            if upper.id in req.pinned:
                req.pinned.append(lower.id)

    def _free_cached_node(self, node) -> None:
        """Evict one cached leaf: release its pages and unlink it (the
        parent becomes a future candidate under its own touch stamp)."""
        self.cache.stats["evicted_nodes"] += 1
        self.cache.stats["evicted_pages"] += self._node_total_pages(node)
        self._release_node_pages(node)
        parent = self.forest.nodes[node.parent]
        parent.children.remove(node.id)
        del self.forest.nodes[node.id]
        self._maybe_free_node(parent)   # frees empty husks, keeps cache

    def _evict_cached(self, min_pages: int) -> int:
        """Evict LRU cache entries until >= ``min_pages`` pages freed
        (or the cache is empty); returns pages actually freed."""
        freed = 0
        while freed < min_pages:
            cands = self.cache.candidates()
            if not cands:
                break
            node = cands[0]
            freed += len(node.page_ids)
            self._free_cached_node(node)
        return freed

    def _detach_finished(self) -> None:
        """Detach completed requests from the forest, retaining their
        page-backed prefix nodes as cache (the tentpole behaviour: a
        finished request's system prompt stays resident for the next
        request that shares it)."""
        done = [r for r in sorted(self.requests)
                if self.requests[r].state == DONE
                and not self.requests[r].kv_freed
                and r in self.forest.leaf_of]
        if not done:
            return
        # cached node tokens are matched by VALUE at future admissions;
        # any in-flight placeholders must land first
        self.flush_tokens()
        for rid in done:
            self._rollback_drafts(rid)
            path = self.forest.path(rid)
            self.forest.detach_request(rid)
            for node in reversed(path):
                if node.id in self.forest.nodes:
                    self._maybe_free_node(node)
            for st in self.mamba_state.values():
                st.pop(rid, None)
            self._mamba_pos.pop(rid, None)
            self.requests[rid].kv_freed = True

    def _cache_sweep(self) -> None:
        """Per-step TTL expiry + LRU enforcement of ``max_pages``."""
        while True:
            expired = [n for n in self.cache.expired()
                       if n.id in self.forest.nodes]
            if not expired:
                break
            for node in expired:    # parents become leaves next round
                if node.id in self.forest.nodes:
                    self._free_cached_node(node)
        over = self.cache.over_cap()
        if over > 0:
            self._evict_cached(over)

    def _stream_ready(self) -> None:
        """Deliver newly-materialised tokens to streaming callbacks
        (stops at the first still-deferred placeholder, so fused-mode
        streams arrive at sync boundaries, in order).

        User callbacks are ISOLATED: one raising ``on_token`` marks only
        that request FAILED (reason ``callback_error``) — the engine
        step, the batch, and every other stream are unaffected."""
        with telemetry_mod.Span("codec.stream"):
            for req in list(self.requests.values()):
                if req.on_token is not None:
                    self._stream_request(req)

    def _stream_request(self, req: Request) -> None:
        gen = req.generated
        while req.emitted < len(gen) and gen[req.emitted] >= 0:
            tok = gen[req.emitted]
            req.emitted += 1
            try:
                if self.injector is not None:
                    spec = self.injector.take("callback", rid=req.rid)
                    if spec is not None:
                        self.stats["faults_injected"] += 1
                        raise InjectedFault(
                            spec, f"injected on_token failure for "
                                  f"request {req.rid}")
                req.on_token(req.rid, tok)
            except Exception:
                self.stats["callback_errors"] += 1
                self._fail_request(req.rid, "callback_error", flush=False)
                break

    def _alloc_pages(self, n: int, exclude: Set[int],
                     allow_preempt: bool = True,
                     hint: Optional[int] = None) -> Optional[List[int]]:
        """Allocate ``n`` pages, evicting under pressure; ``None`` when
        nothing more can be reclaimed (caller stalls or raises).
        ``hint`` (node id) is the sharded pool's placement affinity."""
        if (self.injector is not None and self.spec is None
                and self.injector.take("alloc") is not None):
            # simulated transient exhaustion: callers degrade exactly as
            # under real pressure (stall / preempt-and-recompute), so no
            # committed stream changes.  Gated off in speculative mode:
            # a mid-commit allocation failure there has no clean unwind.
            self.stats["faults_injected"] += 1
            return None
        while self.pool.num_free < n:
            if not self._reclaim_one(exclude, allow_preempt):
                return None
        return self.pool.allocator.alloc(n, hint=hint)

    # ------------------------------------------------------------------ #
    # replication-aware placement (mesh mode): hot short prefix nodes
    # are copied onto EVERY data shard so their rows skip the cross-
    # shard POR merge entirely (core.plan.replicated_node_set decides
    # which rows actually may — a row must be replicated along its WHOLE
    # path, or the merge would LSE-double-count the shared partials)
    # ------------------------------------------------------------------ #
    def _node_total_pages(self, node) -> int:
        """Pool pages the node holds across all shards (replica-aware)."""
        reps = node.meta.get("replicas")
        if reps is not None:
            return sum(len(v) for v in reps.values())
        return len(node.page_ids)

    def _release_node_pages(self, node) -> None:
        """Release every page the node holds (all replicas, or the
        single placement).  ``page_ids`` aliases the primary replica run,
        so replicated nodes must NOT release it separately."""
        reps = node.meta.pop("replicas", None)
        node.meta.pop("replica_primary", None)
        if reps is not None:
            for rws in reps.values():
                self.pool.allocator.release(rws)
        elif node.page_ids:
            self.pool.allocator.release(node.page_ids)
        node.page_ids = []

    def _promote_replicas(self, node) -> bool:
        """Copy a node's KV onto every shard and free its old placement.

        The old pages may be released immediately after the (value-
        semantics) device copy: nothing in the engine retains node pages
        beyond the node itself, so their refcount is 1 by construction.
        """
        alloc = self.pool.allocator
        D = self.pool.num_shards
        n = len(node.page_ids)
        # same tie-break as alloc_replicas' affinity pin
        primary = max(range(D),
                      key=lambda i: (alloc.shards[i].num_free, -i))
        try:
            reps = alloc.alloc_replicas(n, hint=node.id)
        except MemoryError:
            return False
        src = np.asarray(node.page_ids, np.int64)
        dst = np.concatenate([np.asarray(reps[s], np.int64)
                              for s in range(D)])
        srcs = np.tile(src, D)
        self.pool.k = self.pool.k.at[:, dst].set(self.pool.k[:, srcs])
        self.pool.v = self.pool.v.at[:, dst].set(self.pool.v[:, srcs])
        alloc.release(node.page_ids)
        node.meta["replicas"] = reps
        node.meta["replica_primary"] = primary
        node.page_ids = list(reps[primary])
        self.stats["replica_promotions"] += 1
        self._plan_dirty = True
        return True

    def _demote_replicas(self, node) -> None:
        """Back to single placement: keep the primary run, free the rest.
        The running plan's page remaps reference the freed rows, so the
        plan is marked dirty and rebuilt before the next dispatch."""
        reps = node.meta.pop("replicas", None)
        if reps is None:
            return
        primary = node.meta.pop("replica_primary")
        for s, rws in reps.items():
            if s != primary:
                self.pool.allocator.release(rws)
        node.page_ids = list(reps[primary])
        self.stats["replica_demotions"] += 1
        self._plan_dirty = True

    def _replication_sweep(self, rows: List[int]) -> None:
        """Promote nodes whose merge saving beats their extra read cost
        (``CostModel.replicate_gain``), headroom permitting: each shard
        must fit the node AND a page of tail growth per active row."""
        alloc = self.pool.allocator
        D = self.pool.num_shards
        rowset = set(rows)
        seen: Set[int] = set()
        for r in rows:
            for node in self.forest.path(r):
                if node.id in seen:
                    continue
                seen.add(node.id)
                if (not node.page_ids or "replicas" in node.meta
                        or node.meta.get("draft")):
                    continue
                n_q = sum(1 for q in node.requests if q in rowset)
                if n_q == 0:
                    continue
                if self.cost_model.replicate_gain(n_q, node.length, D) <= 0:
                    continue
                n = len(node.page_ids)
                if min(s.num_free for s in alloc.shards) < n + len(rows):
                    continue
                self._promote_replicas(node)

    def _grow_node_pages(self, node, k: int,
                         exclude: Set[int]) -> Optional[List[int]]:
        """Grow a node by ``k`` pages, replica-aware: replicated nodes
        grow on every shard (all-or-nothing), demoting to the primary
        placement when some shard cannot fit — the ordinary reclaiming
        allocator then takes over.  Returns the primary's new rows."""
        reps = node.meta.get("replicas")
        if reps is not None:
            try:
                new = self.pool.allocator.alloc_replicas(k, hint=node.id)
            except MemoryError:
                self._demote_replicas(node)
            else:
                for s, rws in new.items():
                    reps[s].extend(rws)
                primary = node.meta["replica_primary"]
                node.page_ids = list(reps[primary])
                return new[primary]
        got = self._alloc_pages(k, exclude, hint=node.id)
        if got is not None:
            node.page_ids += got
        return got

    # ------------------------------------------------------------------ #
    # measured-cost calibration: refit the cost model's hardware
    # coefficients from the step timings already in ``step_stats``
    # ------------------------------------------------------------------ #
    def recalibrate(self, min_samples: int = 8) -> bool:
        """Fit ``CostModel`` coefficients from measured sharded steps.

        Mesh steps record their plan's feature counts (``hbm_bytes``,
        ``grid_steps``, ``merge_bytes``, ``merge_rounds``) next to the
        measured ``dispatch_time`` (which, under ``calibrate=True``,
        blocks on the device and is the true step wall time).  The fit
        replaces datasheet bandwidths/overheads, so subsequent division,
        lane balancing and replicate-vs-split decisions use measured
        costs.  Steps that hit a compile or an epoch replan are orders
        of magnitude above the steady state and would poison the
        regression, so samples beyond 5x the median step time are
        rejected first.  Returns True when a fit was installed.

        Sampled-profiling rows (``telemetry.profile_every``) carry a
        blocked dispatch/compute split even when ``calibrate=`` is off;
        when any exist they are PREFERRED over plain rows, whose
        ``dispatch_time`` on the async fused path is only the submit
        cost and would poison the fit."""
        rows = [s for s in self.step_stats if s.get("hbm_bytes")]
        profiled = [s for s in rows if s.get("profiled")]
        pool = profiled or [s for s in rows
                            if s.get("dispatch_time", 0) > 0]
        samples = [{**s, "seconds": s["dispatch_time"]
                    + (s.get("compute_time", 0.0)
                       if s.get("profiled") else 0.0)}
                   for s in pool if s.get("dispatch_time", 0) > 0
                   or s.get("compute_time", 0) > 0]
        if samples:
            med = float(np.median([s["seconds"] for s in samples]))
            samples = [s for s in samples if s["seconds"] <= 5.0 * med]
        if self.cost_model.fit(samples, min_samples=min_samples):
            self.stats["calibrations"] += 1
            return True
        return False

    # ------------------------------------------------------------------ #
    # prefill with prefix reuse (chunked, resumable)
    # ------------------------------------------------------------------ #
    def _ensure_pages_upto(self, rid: int, upto: int) -> bool:
        """Allocate pages covering tokens [0, upto) of the path; False when
        allocation stalls (partial allocations are kept for the retry)."""
        for node in self.forest.path(rid):
            cover = min(node.length, max(0, upto - node.start_pos))
            need = -(-cover // self.page_size)
            if len(node.page_ids) < need:
                got = self._grow_node_pages(node,
                                            need - len(node.page_ids),
                                            exclude={rid})
                if got is None:
                    return False
        return True

    def _gather_prefix_upto(self, layer_attn: int, path, upto: int) -> Tuple:
        """Dense (upto, n_kv, hd) of the path's first ``upto`` cached tokens."""
        ks, vs = [], []
        pos = 0
        for node in path:
            take = min(node.length, upto - pos)
            if take <= 0:
                break
            npg = -(-take // self.page_size)
            k, v = self.pool.gather_context(layer_attn,
                                            node.page_ids[:npg], take)
            ks.append(k)
            vs.append(v)
            pos += take
        if not ks:
            hkv, hd = self.cfg.num_kv_heads, self.cfg.head_dim
            z = jnp.zeros((0, hkv, hd), self.pool.k.dtype)
            return z, z
        return jnp.concatenate(ks, 0), jnp.concatenate(vs, 0)

    def _promote(self, req: Request) -> None:
        req.state = RUNNING
        if req.rid in self._prefilling:
            self._prefilling.remove(req.rid)
        self._mamba_pos.pop(req.rid, None)
        if self.telemetry is not None:
            self.telemetry.end(track=req.rid)         # "prefill"
            self.telemetry.begin("decode", track=req.rid)

    def _note_token(self, req: Request) -> None:
        """Telemetry bookkeeping: a committed token value for this
        request just became host-visible (TTFT/TPOT attribution)."""
        now = self.clock()
        if req.first_tok_t is None:
            req.first_tok_t = now
        req.last_tok_t = now

    def _prefill_step(self, req: Request, budget: Optional[int],
                      stop_at: Optional[int] = None,
                      shared_with: Sequence[int] = ()) -> int:
        """Advance the request's prefill by one chunk of ``<= budget``
        tokens (``None`` = the whole remaining prompt); returns tokens
        computed (0 = stalled on pages, retried next step).  ``stop_at``
        additionally caps the chunk at an absolute position — cascade
        phase A uses it to stop exactly at the group's shared-path
        frontier (DESIGN.md §14), and records the chunk on the
        ``shared_with`` siblings' tracks too.

        Attention KV of the cached prefix is reused (gathered from the
        paged pool); SSM layers resume from the deepest cached boundary —
        the carried chunk state, else a node-boundary ``meta["ssm"]``
        cache — and states are (re-)cached at every shared-node boundary
        inside the recomputed span so later siblings resume exactly.
        When the sequence completes, the request joins the decode batch;
        ``pending`` is sampled only if it did not survive a preemption.
        """
        rid = req.rid
        total = len(req.seq)
        path = self.forest.path(rid)

        # contiguous filled-KV front along the path
        kv_filled = self._filled_front(rid)

        has_mamba = any(k.mixer == "mamba" for k in self.layer_kinds)

        if kv_filled < total:
            attn_start = kv_filled
        elif req.pending is None:
            # fully cached prompt: recompute only the final position so
            # its logits exist — the KV itself is resident and nothing
            # needs rewriting.  Recurrent archs still rewind to the
            # deepest cached SSM boundary (mamba_start below), so hybrid
            # spans stay bounded by the last node, not the whole prompt.
            attn_start = total - 1
        else:
            attn_start = total

        mamba_init: Dict[int, Any] = {}
        mamba_start = 0
        if has_mamba:
            carried = self._mamba_pos.get(rid)
            if carried is not None and carried == attn_start:
                mamba_start = carried
                mamba_init = {j: st[rid]
                              for j, st in self.mamba_state.items()
                              if rid in st}
            else:
                pos = 0
                for node in path:
                    f = min(node.meta.get("filled", 0), node.length)
                    pos += node.length
                    if f < node.length or pos > attn_start:
                        break
                    if "ssm" in node.meta:
                        mamba_start, mamba_init = pos, node.meta["ssm"]

        if attn_start >= total and (not has_mamba or mamba_start >= total):
            self._promote(req)
            return 0

        span_start = min(attn_start, mamba_start) if has_mamba \
            else attn_start
        end = total if budget is None else min(
            total, max(span_start + max(budget, 1), kv_filled + 1))
        if stop_at is not None and stop_at < end:
            # never regress below the minimum-progress floor above
            end = max(stop_at, min(kv_filled + 1, total))

        if not self._ensure_pages_upto(rid, end):
            self.stats["prefill_stalls"] += 1
            return 0
        tm = self.telemetry
        with telemetry_mod.PrefillSpan(self.stats, tm, track=rid,
                                       args={"tokens": end - span_start}
                                       ) as sp:
            finished = self._prefill_chunk(req, path, span_start, end,
                                           mamba_init)
        Tn = end - span_start
        if tm is not None:
            for sib in shared_with:
                tm.complete("prefill_chunk", sp.c0, sp.c1, track=sib,
                            args={"tokens": Tn, "shared": True})
            tm.observe("prefill_chunk_s", sp.c1 - sp.c0)
        if finished:
            # after the chunk span closes: the request's "prefill" span
            # ends at promotion and must contain its chunks
            self._promote(req)
        return Tn

    def _prefill_chunk(self, req: Request, path, span_start: int, end: int,
                       mamba_init: Dict[int, Any]) -> bool:
        """Compute positions ``[span_start, end)`` of the request's
        sequence: attention reads the cached prefix's KV, new KV lands in
        the unfilled page slots.  Samples ``pending`` when the sequence
        completes; returns whether it did.  The prefill program
        (``_prefill_program``) runs the chunk where the engine built one,
        else the eager per-layer body (``_prefill_eager``)."""
        total = len(req.seq)
        segments = _span_segments(path, span_start, end)
        # one key split per completing request with no surviving pending
        # token, before either path runs: both sample with the same key
        sk = None
        if end >= total and req.pending is None:
            self.key, sk = jax.random.split(self.key)

        if self._prefill_fn is not None:
            self.stats["prefill_jit_chunks"] += 1
            out = self._prefill_program(req, path, segments, span_start,
                                        end, sk)
        else:
            self.stats["prefill_eager_chunks"] += 1
            out = self._prefill_eager(req, path, segments, span_start, end,
                                      mamba_init)

        Tn = end - span_start
        self.stats["prefill_tokens"] += Tn
        self.stats["recompute_tokens"] += max(
            0, min(end, req.computed_hwm) - span_start)
        req.computed_hwm = max(req.computed_hwm, end)
        if end < total:
            self.stats["prefill_chunks"] += 1
            if self._mamba_layer_js:
                self._mamba_pos[req.rid] = end
            return False

        if sk is not None:
            with telemetry_mod.Span("codec.prefill.sample"):
                if self._prefill_fn is not None:
                    req.pending = int(out)
                else:
                    logits = T._unembed(self.params, self.cfg, out)[0, -1]
                    req.pending = int(sampler.sample(logits[None], sk,
                                                     self.temperature)[0])
        return True

    def _new_kv_slots(self, segments, span_start: int):
        """Pool slots of the chunk's new KV: each covered node's unfilled
        slots, in every replica's pages, which are then marked filled.
        Returns span-local rows and their (page, offset) slots."""
        ps = self.page_size
        rows, pages, offs = [], [], []
        for node, lo, hi in segments:
            start = node.meta.get("filled", 0)
            base = node.start_pos - span_start   # span-local token 0
            t_hi = hi - base
            # a replicated node's KV lands in EVERY shard's replica run
            # (same source row scattered to each), keeping replicas
            # bitwise in sync with the primary
            reps = node.meta.get("replicas")
            page_lists = list(reps.values()) if reps else [node.page_ids]
            for t in range(max(start, lo - base), t_hi):
                for pl in page_lists:
                    pages.append(pl[t // ps])
                    offs.append(t % ps)
                    rows.append(base + t)
            if t_hi > start:
                node.meta["filled"] = t_hi
        return rows, pages, offs

    def _prefill_program(self, req: Request, path, segments,
                         span_start: int, end: int, sk):
        """One dispatch of the prefill program (``step_fn.
        make_prefill_fn``) over the chunk, the pool donated.  The host
        builds small index arrays only: the prefix's pages and each
        slot's KV position (``codec.prefill.gather``), and the rows'
        write slots (``codec.prefill.kv_write``).  Returns the sampled
        token as a device scalar, read only when ``sk`` is given."""
        ps = self.page_size
        trash = self.pool.trash_page
        Tn = end - span_start
        T_pad = plan_mod.bucket_pow2(Tn)
        with telemetry_mod.Span("codec.prefill.gather"):
            pages, pos = [], []
            upto = 0
            for node in path:
                take = min(node.length, span_start - upto)
                if take <= 0:
                    break
                npg = -(-take // ps)
                pages += node.page_ids[:npg]
                slot = np.arange(npg * ps)
                pos.append(np.where(slot < take, upto + slot,
                                    step_fn_mod.NO_POS))
                upto += take
            P_pad = plan_mod.bucket_pow2(len(pages))
            prefix_pages = np.full(P_pad, trash, np.int32)
            prefix_pages[:len(pages)] = pages
            prefix_pos = np.full(P_pad * ps, step_fn_mod.NO_POS, np.int32)
            if pos:
                pos = np.concatenate(pos)
                prefix_pos[:len(pos)] = pos
            tokens = np.zeros(T_pad, np.int32)
            tokens[:Tn] = req.seq[span_start:end]
            positions = np.full(T_pad, -1, np.int32)
            positions[:Tn] = span_start + np.arange(Tn)
        with telemetry_mod.Span("codec.prefill.kv_write"):
            rows, w_pages, w_offs = self._new_kv_slots(segments, span_start)
            write_page = np.full(T_pad, trash, np.int32)
            write_off = np.zeros(T_pad, np.int32)
            write_page[rows] = w_pages
            write_off[rows] = w_offs
        with telemetry_mod.Span("codec.prefill.attn"):
            self.prefill_signatures.add((T_pad, P_pad))
            self.stats["prefill_signatures"] = len(self.prefill_signatures)
            tok, self.pool.k, self.pool.v = self._prefill_fn(
                self.params, self.pool.k, self.pool.v, tokens, positions,
                prefix_pages, prefix_pos, write_page, write_off,
                np.int32(Tn - 1), self.key if sk is None else sk)
        return tok

    def _prefill_eager(self, req: Request, path, segments, span_start: int,
                       end: int, mamba_init: Dict[int, Any]):
        """The chunk op by op, one layer at a time (recurrent stacks and
        the mesh pool).  Returns the last layer's hidden states."""
        cfg = self.cfg
        rid = req.rid
        tokens = np.asarray(req.seq[span_start:end], np.int32)
        Tn = len(tokens)
        positions = (span_start + np.arange(Tn))[None]           # (1, Tn)

        x = T._embed(self.params, cfg, jnp.asarray(tokens)[None],
                     jnp.asarray(positions))
        leaf_id = self.forest.leaf_of[rid]

        new_kv_writes = []  # (layer_attn, k (Tn,kv,hd), v)
        for j, kind in enumerate(self.layer_kinds):
            p = layer_params(cfg, self.params, j)
            h = L.apply_norm(p["ln"], x, cfg)
            if kind.mixer in ("attn", "attn_local"):
                la = self.attn_layer_idx[j]
                window = (cfg.sliding_window if kind.mixer == "attn_local"
                          else 0)
                q, k_new, v_new = L.attn_project(p["attn"], cfg, h,
                                                 jnp.asarray(positions))
                with telemetry_mod.Span("codec.prefill.gather"):
                    pk, pv = self._gather_prefix_upto(la, path, span_start)
                    k_all = jnp.concatenate([pk.astype(k_new.dtype)[None],
                                             k_new], 1)
                    v_all = jnp.concatenate([pv.astype(v_new.dtype)[None],
                                             v_new], 1)
                with telemetry_mod.Span("codec.prefill.attn"):
                    o = L.mha(q, k_all, v_all, causal=True, window=window,
                              softcap=cfg.attn_logit_softcap,
                              q_positions=jnp.asarray(positions),
                              kv_positions=jnp.arange(end)[None])
                y = L.dense(p["attn"]["wo"],
                            o.reshape(1, Tn, cfg.num_heads * cfg.head_dim))
                new_kv_writes.append((la, k_new[0], v_new[0]))
                x = x + y
            elif kind.mixer == "mamba":
                state = mamba_init.get(j)
                ys = []
                for node, lo, hi in segments:
                    y_seg, state = self._mamba_prefill(p["mamba"],
                                                       h[:, lo:hi], state)
                    ys.append(y_seg)
                    # cache end-of-node state (shared nodes only, and only
                    # when the chunk reaches the node boundary; a leaf's
                    # state keeps moving, carried per request below)
                    if (node.id != leaf_id
                            and span_start + hi == node.end_pos):
                        node.meta.setdefault("ssm", {})[j] = state
                y = jnp.concatenate(ys, 1)
                self.mamba_state.setdefault(j, {})[rid] = state
                x = x + y
            x, _ = L.apply_ffn_block(p, cfg, kind.ffn, x)

        # write new KV into unfilled page slots only
        with telemetry_mod.Span("codec.prefill.kv_write"):
            kv_rows, pages, offs = self._new_kv_slots(segments, span_start)
            if kv_rows:
                rows = jnp.asarray(np.asarray(kv_rows))
                for la, k_new, v_new in new_kv_writes:
                    self.pool.write_tokens(la, np.asarray(pages),
                                           np.asarray(offs),
                                           k_new[rows], v_new[rows])
        return x

    def _mamba_prefill(self, p, h, init):
        cfg = self.cfg
        if init is None:
            return M.mamba_forward(p, cfg, h)
        conv0, ssm0 = init
        # run chunked SSD from a carried state
        zxbcdt = h @ p["in_proj"]["w"]
        z, xBC_raw, dt = M._split_proj(cfg, zxbcdt)
        xBC = M._causal_conv(xBC_raw, p["conv_w"], p["conv_b"],
                             init_state=conv0)
        d_in, S = cfg.d_inner, cfg.ssm_state
        B, Tn = h.shape[0], h.shape[1]
        x_ssm = xBC[..., :d_in].reshape(B, Tn, cfg.ssm_heads,
                                        cfg.ssm_head_dim)
        Bm = xBC[..., d_in:d_in + S]
        Cm = xBC[..., d_in + S:]
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        y, final = M.ssd_chunked(x_ssm, dt, A, Bm, Cm, cfg.ssm_chunk,
                                 init_state=ssm0)
        y = y + x_ssm.astype(y.dtype) * p["D"][None, None, :, None].astype(y.dtype)
        y = y.reshape(B, Tn, d_in)
        y = M._gated_norm(y, z, p["norm"], cfg.norm_eps)
        out = y @ p["out_proj"]["w"]
        K = cfg.ssm_conv
        conv_tail = jnp.concatenate([conv0, xBC_raw.astype(jnp.float32)],
                                    1)[:, -(K - 1):]
        return out, (conv_tail, final)

    # ------------------------------------------------------------------ #
    # plan management
    # ------------------------------------------------------------------ #
    def _windows(self) -> List[int]:
        ws = set()
        for kind in self.layer_kinds:
            if kind.mixer == "attn":
                ws.add(0)
            elif kind.mixer == "attn_local":
                ws.add(self.cfg.sliding_window)
        return sorted(ws)

    @property
    def plan_rebuilds(self) -> int:
        """Rebuild counter (the plan-lifecycle tests consume this)."""
        return self.stats["replans"]

    @property
    def fused_cache_size(self) -> int:
        """Compiled fused-step program count (jit cache entries); the
        compile-cache regression test bounds this by the number of
        distinct ``bucket_signatures``."""
        # _cache_size is a private jax API; degrade to 0 rather than
        # crash stats printing if a future jax renames it
        fn = self._step_fn if self._step_fn is not None \
            else self._spec_step_fn
        size = getattr(fn, "_cache_size", None)
        return int(size()) if callable(size) else 0

    def lower_fused_step(self):
        """Lower the fused decode step at the current epoch's shapes.

        Lowers only (nothing is dispatched or donated); the text shows
        what the step contains, e.g. a ``tpu_custom_call`` per compiled
        Pallas kernel.  Needs a fused engine that has decoded a step.
        """
        if self._step_fn is None or self._fused_base is None:
            raise RuntimeError("no fused decode step has run yet")
        state = step_fn_mod.StepState(self.pool.k, self.pool.v,
                                      *self._mamba_carry)
        tokens = jnp.zeros(self._fused_bucket, jnp.int32)
        return self._step_fn.lower(
            self.params, state, tokens, self.key, self._fused_base,
            np.int32(self._fused_delta), self._fused_prepared)

    def _rebuild_plans(self) -> None:
        rows = self._active_rows()
        tm = self.telemetry
        with telemetry_mod.Span("codec.plan_build", tm, "plan_build",
                                args={"rows": len(rows)}) as sp:
            self._build_plans(rows)
        self.stats["replans"] += 1
        self.stats["plan_time"] += sp.seconds
        if tm is not None:
            tm.observe("plan_build_s", sp.c1 - sp.c0)

    def _build_plans(self, rows: List[int]) -> None:
        req_rows = {r: i for i, r in enumerate(rows)}
        ps = self.page_size
        truncate = {}
        for r in rows:
            leaf = self.forest.nodes[self.forest.leaf_of[r]]
            tail_start = max(0, ((leaf.length - 1) // ps) * ps)
            truncate[leaf.id] = tail_start
        build = (plan_mod.flash_plan if self._backend.plan_kind == "flash"
                 else plan_mod.build_plan)
        self._plans = {}
        for w in self._windows():
            p = build(
                self.forest, self.cost_model, self.num_lanes, self.max_q,
                self.max_kv_per_task, req_rows=req_rows, window=w,
                truncate=truncate)
            p = plan_mod.pad_plan(p)
            self._plans[w] = (p, self._backend.prepare(p))
        self._plan_key = plan_mod.plan_key(self.forest, rows)
        self._plan_dirty = False
        self._steps_since_plan = 0

    def _advance_qpos(self) -> None:
        """Cheap per-step plan refresh: live queries moved one position."""
        for w, (p, _) in list(self._plans.items()):
            slot = np.arange(p.max_q)[None, :]
            live = slot < p.task_qnum[:, None]
            p.q_pos = p.q_pos + live.astype(np.int32)
            self._plans[w] = (p, self._backend.prepare(p))

    # ------------------------------------------------------------------ #
    # decode step (admit -> prefill -> decode -> evict state machine)
    # ------------------------------------------------------------------ #
    def step(self) -> Dict[int, int]:
        """One engine step: admission + chunked prefill, then append
        pending tokens (evicting under pressure) and decode one token per
        running request."""
        tm = self.telemetry
        n = len(self.step_stats)
        with telemetry_mod.Span("codec.step", tm, "step", args={"step": n},
                                step=n) as sp:
            out = self._step()
            sp.args["decoded"] = len(out)
        if tm is not None:
            tm.observe("step_s", sp.c1 - sp.c0)
        return out

    def _step(self) -> Dict[int, int]:
        tm = self.telemetry
        snap = {k: self.stats[k]
                for k in ("steps", "admitted", "preempted", "reclaimed",
                          "prefill_tokens", "recompute_tokens",
                          "spec_proposed", "spec_accepted",
                          "cancelled", "timed_out", "failed",
                          "callback_errors", "faults_injected",
                          "dispatch_failures", "dispatch_recoveries")}
        # per-step timing starts HERE: a flush triggered by deadline
        # enforcement or between-step admission bills this step's
        # flush_time, never the decode phase's dispatch/compute split
        self._decode_timing = {}
        if self.injector is not None:
            self.injector.tick(len(self.step_stats))
        self._enforce_deadlines()
        self._admit_phase()
        out = self._decode_with_recovery()
        if self.cache is not None:
            self.cache.tick()
            self._detach_finished()
            self._cache_sweep()
        self._stream_ready()
        self._notify_done()
        if self.check_every and (len(self.step_stats) + 1) \
                % self.check_every == 0:
            self.check()
        cache_stats = {}
        if self.cache is not None:
            d = self._cache_step_delta()
            resident = self.cache.resident_pages()
            cache_stats = {
                "cache_hits": d["hits"],
                "cache_hit_rate": self.cache.hit_rate,
                "cache_resident_pages": resident,
                "cache_resident_bytes": resident * self.pool.page_bytes,
                "cache_evicted_nodes": d["evicted_nodes"],
            }
        self.step_stats.append({
            "step": len(self.step_stats),
            "decoded": len(out),
            **self._decode_timing,
            "admitted": self.stats["admitted"] - snap["admitted"],
            "preempted": self.stats["preempted"] - snap["preempted"],
            "reclaimed": self.stats["reclaimed"] - snap["reclaimed"],
            "prefill_tokens": (self.stats["prefill_tokens"]
                               - snap["prefill_tokens"]),
            "recompute_tokens": (self.stats["recompute_tokens"]
                                 - snap["recompute_tokens"]),
            **({"spec_proposed": (self.stats["spec_proposed"]
                                  - snap["spec_proposed"]),
                "spec_accepted": (self.stats["spec_accepted"]
                                  - snap["spec_accepted"])}
               if self.spec is not None else {}),
            "waiting": len(self.admission),
            "prefilling": len(self._prefilling),
            "running": len(self._active_rows()),
            "pages_free": self.pool.num_free,
            "occupancy": self.pool.occupancy(),
            **{k: self.stats[k] - snap[k]
               for k in ("cancelled", "timed_out", "failed",
                         "callback_errors", "faults_injected",
                         "dispatch_failures", "dispatch_recoveries")},
            **cache_stats,
        })
        if tm is not None:
            tm.metrics["engine_steps"].inc()
            if self.mesh is not None and self._epoch_features \
                    and self.stats["steps"] > snap["steps"]:
                tm.metrics["merge_bytes"].inc(
                    self._epoch_features["merge_bytes"])
                tm.metrics["merge_rounds"].inc(
                    self._epoch_features["merge_rounds"])
            t = self._decode_timing
            if "dispatch_time" in t:
                tm.observe("dispatch_s", t["dispatch_time"])
            if t.get("profiled"):
                tm.observe("profile_dispatch_s", t["dispatch_time"])
                tm.observe("profile_device_s", t.get("compute_time", 0.0))
                tm.observe("profile_host_s", t.get("host_time", 0.0))
            self._publish_telemetry()
        return out

    def _cache_step_delta(self) -> Dict[str, int]:
        """Advance the rolling cache-stats snapshot and return the
        delta since the previous step — read-and-update is ATOMIC here,
        the single consumer, so lookups recorded by eager between-step
        admissions land in exactly one step row no matter how often
        external readers poll ``step_stats`` or the metrics registry
        (those readers difference their own snapshots instead)."""
        cur = dict(self.cache.stats)
        prev = self._cache_snap
        self._cache_snap = cur
        return {k: cur[k] - prev.get(k, 0) for k in cur}

    def _publish_telemetry(self) -> None:
        """Fold cumulative engine/cache stats into the metrics registry
        (monotone counter deltas) and refresh the gauges.  Runs every
        step and before any metrics export."""
        tm = self.telemetry
        if tm is None:
            return
        tm.sync_counters("engine", self.stats,
                         telemetry_mod.ENGINE_STAT_COUNTERS)
        gauges = {
            "pool_occupancy": self.pool.occupancy(),
            "pool_free_pages": self.pool.num_free,
            "backoff_pages": self._backoff_pages,
            "running": len(self._active_rows()),
            "waiting": len(self.admission),
            "prefilling": len(self._prefilling),
        }
        if self.cache is not None:
            tm.sync_counters("cache", self.cache.stats,
                             telemetry_mod.CACHE_STAT_COUNTERS)
            resident = self.cache.resident_pages()
            gauges.update(cache_hit_rate=self.cache.hit_rate,
                          cache_resident_pages=resident,
                          cache_resident_bytes=resident
                          * self.pool.page_bytes)
        if self.fused:
            gauges["compile_count"] = self.fused_cache_size
        tm.set_gauges(gauges)

    def publish_metrics(self):
        """Public sync point for registry readers (benchmarks, serve):
        returns the up-to-date :class:`~repro.core.metrics
        .MetricsRegistry`, or None when telemetry is off."""
        if self.telemetry is None:
            return None
        self._publish_telemetry()
        return self.telemetry.metrics

    def export_metrics(self, path: str, extra=None) -> None:
        """Sync and write the schema-tagged metrics JSON."""
        if self.telemetry is None:
            raise RuntimeError(
                "export_metrics needs DecodeEngine(telemetry=...)")
        self._publish_telemetry()
        self.telemetry.export_metrics(path, extra=extra)

    def _decode_with_recovery(self) -> Dict[int, Optional[int]]:
        """Dispatch the decode phase under the degradation ladder.

        A recoverable dispatch failure (``ResourceExhausted`` — the
        analogue of XLA's RESOURCE_EXHAUSTED, raised by a backend or the
        fault injector) walks one ladder rung per retry: demote replicas
        -> evict cached nodes -> generic reclaim/preempt -> shrink
        admission.  Each recovery is followed by a full invariant
        self-check; ``max_dispatch_retries`` bounds the walk, after
        which the error propagates (genuinely fatal)."""
        for attempt in range(self.max_dispatch_retries + 1):
            try:
                return self._decode_phase()
            except ResourceExhausted:
                self.stats["dispatch_failures"] += 1
                if (attempt >= self.max_dispatch_retries
                        or not self._recover_dispatch()):
                    raise
                self.stats["dispatch_recoveries"] += 1
                self._plan_dirty = True
                self.check()
        return {}

    def _recover_dispatch(self) -> bool:
        """One rung of the degradation ladder; ``True`` if anything gave."""
        repl = [n for n in self.forest.nodes.values()
                if "replicas" in n.meta]
        if repl:
            self._demote_replicas(max(repl,
                                      key=lambda n: len(n.page_ids)))
            return True
        if self.cache is not None and self._evict_cached(1) > 0:
            return True
        if self._reclaim_one(set(), allow_preempt=True):
            return True
        if self._backoff_pages < self.pool.num_pages:
            self._backoff_pages += max(1, self.pool.num_pages // 16)
            return True
        return False

    def _decode_phase(self) -> Dict[int, Optional[int]]:
        if self.injector is not None:
            spec = self.injector.take("stall")
            if spec is not None:
                # latency fault (a slow mesh shard / host hiccup): the
                # engine just rides it out — streams are unaffected
                self.stats["faults_injected"] += 1
                time.sleep(float(spec.payload) or 0.002)
            spec = self.injector.take("dispatch")
            if spec is not None:
                # raised BEFORE any state mutation, like a backend OOM
                # surfacing at dispatch: the retry re-enters cleanly
                self.stats["faults_injected"] += 1
                raise ResourceExhausted(
                    f"injected dispatch failure at step "
                    f"{len(self.step_stats)}: RESOURCE_EXHAUSTED "
                    f"(simulated)")
        if self.spec is not None:
            return self._decode_phase_spec()
        if self.fused:
            return self._decode_phase_fused()
        return self._decode_phase_eager()

    def _grow_leaf_tail(self, r: int):
        """Ensure the request's leaf has a page slot for its newest
        token, preempting under pressure (``exclude={r}``); returns the
        leaf.  Shared by the normal append path and the speculative
        commit so their growth/eviction behaviour can never diverge."""
        leaf = self.forest.nodes[self.forest.leaf_of[r]]
        if -(-leaf.length // self.page_size) > len(leaf.page_ids):
            got = self._grow_node_pages(leaf, 1, exclude={r})
            if got is None:
                raise MemoryError(
                    f"KV pool exhausted growing request {r}: nothing "
                    f"left to evict (pool smaller than the working set)")
        return leaf

    def _append_pending(self, rows0: List[int]) -> None:
        """Append each running request's pending token to its leaf and
        grow tail pages, preempting the fewest-generated victim when the
        pool runs dry.  Device pendings (fused async path) are appended
        as placeholders and patched at the next ``flush_tokens``."""
        for r in rows0:
            req = self.requests[r]
            if req.state != RUNNING:   # evicted growing an earlier row
                continue
            if req.pending is None:    # dispatch-retry re-entry: this
                continue               # row already appended this step
            if req.pending is PENDING_DEVICE:
                ent, row = self._pending_ref.pop(r)
                self.forest.append_token(r, _PLACEHOLDER)
                leaf = self.forest.nodes[self.forest.leaf_of[r]]
                ent.patches.append((r, row, len(req.generated), leaf.id,
                                    len(leaf.tokens) - 1))
                req.generated.append(_PLACEHOLDER)
            else:
                self.forest.append_token(r, req.pending)
                req.generated.append(req.pending)
                if self.telemetry is not None:
                    self._note_token(req)
            req.pending = None
            try:
                self._grow_leaf_tail(r)
            except MemoryError:
                # nothing reclaimable right now.  With other tenants the
                # pressure is transient: preempt-and-recompute keeps the
                # greedy stream byte-identical.  A lone request can never
                # get more room — fail it instead of livelocking.
                others = [q for q in self.requests.values()
                          if q.rid != r
                          and q.state in (WAITING, PREFILL, RUNNING)]
                if others:
                    self._preempt(r)
                else:
                    self._fail_request(r, "kv_exhausted", flush=False)

    def _decode_phase_eager(self) -> Dict[int, int]:
        cfg = self.cfg
        rows0 = self._active_rows()
        if not rows0:
            return {}
        with telemetry_mod.Span("codec.decode", self.telemetry,
                                "decode") as sp:
            flush_before = self._decode_timing.get("flush_time", 0.0)
            # 1. append pending tokens to leaves (may evict under pressure)
            self._append_pending(rows0)
            rows = self._active_rows()
            if not rows:
                sp.event = None                 # nothing decoded: no event
                return {}
            tokens = [self.requests[r].generated[-1] for r in rows]

            # 2. plan lifecycle: rebuild exactly when the plan key changed
            #    (membership, path structure, tail page) or on the interval
            if (self.replan_interval is not None
                    and self._steps_since_plan >= self.replan_interval):
                self._plan_dirty = True
            if (self._plan_dirty
                    or plan_mod.plan_key(self.forest, rows) != self._plan_key):
                self._rebuild_plans()
            else:
                self._advance_qpos()
            self._steps_since_plan += 1

            B = len(rows)
            ctx = np.array([self.forest.context_len(r) for r in rows],
                           np.int32)
            q_pos = jnp.asarray(ctx - 1)
            x = T._embed(self.params, cfg, jnp.asarray(tokens)[None].T,
                         q_pos[:, None])                       # (B,1,d)

            # tail page info, converted host->device ONCE per step (not once
            # per attention layer)
            tail_pages, tail_base, tail_off = [], [], []
            for i, r in enumerate(rows):
                leaf = self.forest.nodes[self.forest.leaf_of[r]]
                tp = (leaf.length - 1) // self.page_size
                tail_pages.append(leaf.page_ids[tp])
                tail_base.append(leaf.start_pos + tp * self.page_size)
                tail_off.append((leaf.length - 1) % self.page_size)
            tail_pages = jnp.asarray(np.asarray(tail_pages), jnp.int32)
            tail_base = jnp.asarray(np.asarray(tail_base), jnp.int32)
            tail_off = jnp.asarray(np.asarray(tail_off), jnp.int32)

            for j, kind in enumerate(self.layer_kinds):
                p = layer_params(cfg, self.params, j)
                h = L.apply_norm(p["ln"], x, cfg)
                if kind.mixer in ("attn", "attn_local"):
                    la = self.attn_layer_idx[j]
                    window = (cfg.sliding_window if kind.mixer == "attn_local"
                              else 0)
                    q, k_new, v_new = L.attn_project(p["attn"], cfg, h,
                                                     q_pos[:, None])
                    self.pool.write_tokens(la, tail_pages, tail_off,
                                           k_new[:, 0], v_new[:, 0])
                    k_pool, v_pool = self.pool.layer_pools(la)
                    qb = q[:, 0]                                # (B, h, hd)
                    o = self._attend(qb, k_pool, v_pool, window, B,
                                     tail_pages, tail_base, q_pos)
                    y = L.dense(p["attn"]["wo"],
                                o.reshape(B, 1, cfg.num_heads * cfg.head_dim))
                    x = x + y
                elif kind.mixer == "mamba":
                    states = self.mamba_state[j]
                    conv = jnp.concatenate([states[r][0] for r in rows], 0)
                    ssm = jnp.concatenate([states[r][1] for r in rows], 0)
                    y, (conv_n, ssm_n) = M.mamba_decode(p["mamba"], cfg, h,
                                                        conv, ssm)
                    for i, r in enumerate(rows):
                        states[r] = (conv_n[i:i + 1], ssm_n[i:i + 1])
                    x = x + y
                x, _ = L.apply_ffn_block(p, cfg, kind.ffn, x)

            logits = T._unembed(self.params, cfg, x)[:, 0]      # (B, V)
            if self.injector is not None:
                spec = self.injector.take("nan_logits")
                if spec is not None:
                    target = spec.rid if spec.rid in rows else rows[0]
                    logits = logits.at[rows.index(target)].set(jnp.nan)
                    self.stats["faults_injected"] += 1
            self.key, sk = jax.random.split(self.key)
            toks_dev = sampler.sample(logits, sk, self.temperature)
            t1 = time.perf_counter()
            # dispatch is done; the timer must cover the actual compute too
            toks = np.asarray(jax.block_until_ready(toks_dev))
            t2 = time.perf_counter()
            bad_rows: List[int] = []
            if self.nan_guard:
                # the eager path syncs every step anyway, so a host-side
                # finite check costs one extra small transfer
                okv = np.asarray(jnp.isfinite(logits).all(-1))
                bad_rows = [r for i, r in enumerate(rows) if not okv[i]]
            out = {}
            for i, r in enumerate(rows):
                if r in bad_rows:
                    continue
                req = self.requests[r]
                req.pending = int(toks[i])
                req.computed_hwm = max(req.computed_hwm, int(ctx[i]))
                out[r] = int(toks[i])
                if len(req.generated) >= req.max_new:
                    req.state = DONE
            for r in bad_rows:
                # quarantine: the poisoned token never enters the stream,
                # the batch keeps decoding without the failed row
                self.stats["nan_rows"] += 1
                self._fail_request(r, "nan_logits", flush=False)
            self.stats["steps"] += 1
            # any flush that ran inside this phase (preempting appends) has
            # billed flush_time already; keep it out of the dispatch split
            flush_in = self._decode_timing.get("flush_time", 0.0) \
                - flush_before
            self._decode_timing.update(
                dispatch_time=max(0.0, t1 - sp.t0 - flush_in),
                compute_time=t2 - t1)
            self.stats["decode_dispatch_time"] += \
                self._decode_timing["dispatch_time"]
            sp.args = {"mode": "eager", "rows": len(rows)}
        return out

    def _attend(self, qb, k_pool, v_pool, window, B,
                tail_pages, tail_base, q_pos):
        plan, prepared = self._plans[window]
        # frozen part: backend partials over all full pages
        o_f, m_f, l_f = self._backend.partials(
            qb, k_pool, v_pool, plan, prepared, window=window)
        # tail part: each request's growing last page
        kt = k_pool[tail_pages]
        vt = v_pool[tail_pages]
        o_t, m_t, l_t = ops.single_page_attention(
            qb, kt, vt, tail_base, q_pos, window=window)
        o, _, _ = ref_mod.por_ref(o_f, m_f, l_f, o_t, m_t, l_t)
        return o.astype(qb.dtype)

    # ------------------------------------------------------------------ #
    # fused decode phase: one jitted, donated, bucketed dispatch per
    # token; host syncs only at plan-rebuild/admission/eviction/
    # completion boundaries (serving/step_fn.py, DESIGN.md §8)
    # ------------------------------------------------------------------ #
    def _decode_phase_fused(self) -> Dict[int, Optional[int]]:
        rows0 = self._active_rows()
        if not rows0:
            return {}
        tm = self.telemetry
        with telemetry_mod.Span("codec.decode", tm, "decode") as sp:
            flush_before = self._decode_timing.get("flush_time", 0.0)
            # sampled profiling (telemetry.profile_every): this step blocks
            # on the device to split dispatch/device/host phases; unsampled
            # steps stay on the async fast path untouched
            profiled = tm is not None \
                and tm.should_profile(len(self.step_stats))
            # 1. append pending tokens (host ints after a sync / prefill,
            #    otherwise the in-flight device array via placeholders)
            self._append_pending(rows0)
            rows = self._active_rows()
            if not rows:
                sp.event = None                 # nothing decoded: no event
                return {}

            # 2. plan lifecycle: a rebuild is the sync point — deferred
            #    tokens land, batched SSM state scatters back, plans/base
            #    arrays are rebuilt bucketed
            if (self.replan_interval is not None
                    and self._steps_since_plan >= self.replan_interval):
                self._plan_dirty = True
            if (self._plan_dirty or self._fused_rows != rows
                    or plan_mod.plan_key(self.forest, rows) != self._plan_key):
                self._fused_epoch(rows)
            else:
                self._fused_delta += 1
            self._steps_since_plan += 1

            # 3. input tokens: in steady state the previous dispatch's device
            #    array (no host round-trip); after any sync, host values
            if (not self._flushed_since_dispatch and self._last_out is not None
                    and self._last_out[0] == rows):
                tok_in = self._last_out[1]
            else:
                tok = np.zeros(self._fused_bucket, np.int32)
                tok[:len(rows)] = [self.requests[r].generated[-1]
                                   for r in rows]
                tok_in = jnp.asarray(tok)
                if self._replicated_sharding is not None:
                    tok_in = jax.device_put(tok_in, self._replicated_sharding)

            # injected NaN: corrupt one KV slot of the target's PRIVATE leaf
            # so the dispatch's attention reads it, poisons that row's
            # logits, and the row_ok flag catches it at the next flush —
            # exercising the real corruption path, not a shortcut
            if self.injector is not None:
                spec = self.injector.take("nan_logits")
                if spec is not None:
                    target = spec.rid if spec.rid in rows else rows[0]
                    leaf = self.forest.nodes[self.forest.leaf_of[target]]
                    if (leaf.length >= 2 and len(leaf.requests) == 1
                            and not leaf.children):
                        slot = leaf.length - 2
                        page = leaf.page_ids[slot // self.page_size]
                        off = slot % self.page_size
                        self.pool.k = self.pool.k.at[:, page, off].set(
                            jnp.nan)
                        self._nan_dirty.append((page, off))
                        self.stats["faults_injected"] += 1
                    else:       # leaf shared or too short: try again later
                        self.injector.requeue(spec)

            # 4. single dispatch: layers + KV writes + attention + merge +
            #    FFN + unembed + sampling, pool/SSM state donated
            conv_all, ssm_all = self._mamba_carry
            state = step_fn_mod.StepState(self.pool.k, self.pool.v,
                                          conv_all, ssm_all)
            with telemetry_mod.Span("codec.dispatch") as sd:
                if self.mesh is not None:
                    # the sharded step fn has no row_ok output (nan_guard is
                    # rejected with a mesh at construction)
                    toks_dev, self.key, state = self._step_fn(
                        self.params, state, tok_in, self.key, self._fused_base,
                        np.int32(self._fused_delta), self._fused_prepared)
                    ok_dev = None
                else:
                    toks_dev, ok_dev, self.key, state = self._step_fn(
                        self.params, state, tok_in, self.key, self._fused_base,
                        np.int32(self._fused_delta), self._fused_prepared)
            calibrating = self.calibrate and self.mesh is not None
            t_d2 = sd.t1
            if calibrating or profiled:
                # calibration/profiling fit against TRUE step seconds, so
                # the async dispatch must block here (costs the overlap;
                # opt-in — calibrate blocks every step, profile_every only
                # the sampled ones)
                jax.block_until_ready(toks_dev)
                t_d2 = time.perf_counter()
            # calibrate keeps its historical meaning: dispatch_time is the
            # full blocked step.  Profiled steps split submit vs device.
            dispatch = (t_d2 if calibrating else sd.t1) - sd.t0
            self.pool.k, self.pool.v = state.pool_k, state.pool_v
            self._mamba_carry = (state.conv, state.ssm)
            ent = _Deferred(toks_dev, list(rows),
                            ok=ok_dev if self.nan_guard else None)
            self._deferred.append(ent)
            self._last_out = (list(rows), toks_dev)
            self._flushed_since_dispatch = False
            out: Dict[int, Optional[int]] = {}
            done_any = False
            for i, r in enumerate(rows):
                req = self.requests[r]
                req.pending = PENDING_DEVICE
                self._pending_ref[r] = (ent, i)
                req.computed_hwm = max(req.computed_hwm,
                                       self.forest.context_len(r))
                out[r] = None
                if len(req.generated) >= req.max_new:
                    req.state = DONE
                    done_any = True
            self.stats["steps"] += 1
            self.stats["fused_calls"] += 1
            self.stats["decode_dispatch_time"] += dispatch
            flush_in = self._decode_timing.get("flush_time", 0.0) \
                - flush_before
            self._decode_timing.update(dispatch_time=dispatch)
            if profiled and not calibrating:
                self._decode_timing.update(
                    compute_time=t_d2 - sd.t1, profiled=True,
                    host_time=max(0.0, sd.t0 - sp.t0 - flush_in))
            if self.mesh is not None and self._epoch_features:
                self._decode_timing.update(self._epoch_features)
                if profiled and self._epoch_shard_features:
                    # per-shard attribution of the sampled step (feeds
                    # CostModel.fit / imbalance analysis downstream)
                    self._decode_timing.update(self._epoch_shard_features)
            if done_any:
                # completion boundary: finished streams must be readable
                self.flush_tokens()
                for r in rows:
                    if self.requests[r].done:
                        out[r] = self.requests[r].generated[-1]
            sp.args = {"mode": "fused", "rows": len(rows),
                       "profiled": bool(profiled)}
        return out

    def _fused_epoch(self, rows: List[int]) -> None:
        """Start a new plan epoch (the fused path's only sync point)."""
        self.flush_tokens()
        self._sync_mamba_state()
        tm = self.telemetry
        with telemetry_mod.Span("codec.plan_build", tm, "plan_build") as sp:
            self._build_epoch(rows)
            sp.args = {"rows": len(rows), "bucket": self._fused_bucket}
        self.stats["replans"] += 1
        self.stats["plan_time"] += sp.seconds
        if tm is not None:
            tm.observe("plan_build_s", sp.c1 - sp.c0)

    def _build_epoch(self, rows: List[int]) -> None:
        B = len(rows)
        bucket = plan_mod.bucket_pow2(B)
        req_rows = {r: i for i, r in enumerate(rows)}
        ps = self.page_size
        truncate = {}
        for r in rows:
            leaf = self.forest.nodes[self.forest.leaf_of[r]]
            truncate[leaf.id] = max(0, ((leaf.length - 1) // ps) * ps)
        if self.mesh is not None:
            self._sharded_epoch(rows, bucket, req_rows, truncate)
        else:
            build = (plan_mod.flash_plan
                     if self._backend.plan_kind == "flash"
                     else plan_mod.build_plan)
            prepared = []
            sig: List = [bucket]
            for w in self._windows():
                p = build(self.forest, self.cost_model, self.num_lanes,
                          self.max_q, self.max_kv_per_task,
                          req_rows=req_rows, window=w, truncate=truncate)
                p = plan_mod.bucket_plan(p, bucket)
                pr = self._backend.prepare(p)
                prepared.append(pr)
                sig.append((w,) + tuple(tuple(a.shape)
                                        for a in jax.tree.leaves(pr)))
            self._fused_prepared = tuple(prepared)
            self.bucket_signatures.add(tuple(sig))

            valid = np.zeros(bucket, bool)
            valid[:B] = True
            q_pos0 = np.full(bucket, -1, np.int32)
            tail_page = np.full(bucket, self.pool.trash_page, np.int32)
            tail_base = np.zeros(bucket, np.int32)
            tail_off0 = np.zeros(bucket, np.int32)
            for i, r in enumerate(rows):
                q_pos0[i] = self.forest.context_len(r) - 1
                leaf = self.forest.nodes[self.forest.leaf_of[r]]
                tp = (leaf.length - 1) // ps
                tail_page[i] = leaf.page_ids[tp]
                tail_base[i] = leaf.start_pos + tp * ps
                tail_off0[i] = (leaf.length - 1) % ps
            self._fused_base = step_fn_mod.StepBase(
                jnp.asarray(valid), jnp.asarray(q_pos0),
                jnp.asarray(tail_page), jnp.asarray(tail_base),
                jnp.asarray(tail_off0))
        self._fused_rows = list(rows)
        self._fused_bucket = bucket
        self._fused_delta = 0
        self._gather_mamba_state(rows, bucket)
        self._plan_key = plan_mod.plan_key(self.forest, rows)
        self._plan_dirty = False
        self._steps_since_plan = 0

    def _sharded_epoch(self, rows: List[int], bucket: int,
                       req_rows: Dict[int, int],
                       truncate: Dict[int, int]) -> None:
        """Mesh-mode epoch: per-shard plans + stacked SPMD step inputs.

        One ``DecodePlan`` per data shard (subtasks forced to the shard
        holding their pages, sequence splits cut at shard boundaries —
        ``core.plan.build_sharded_plan``), all bucketed to COMMON shapes
        so the prepared arrays stack into ``(D, ...)`` inputs; the tail
        layout becomes per-shard local page rows (non-owners point at
        their shard's trash page).
        """
        from ..distributed import step_fn as sharded_step_fn_mod
        B = len(rows)
        ps = self.page_size
        D = self.pool.num_shards
        stride = self.pool.page_stride
        if self.replicate:
            self._replication_sweep(rows)
        if self.calibrate:
            # refit hardware coefficients from the measured steps so the
            # plans built below divide/balance/replicate on real costs
            self.recalibrate()
        self.pool.canonicalize()
        prepared = []
        sig: List = [("mesh", D, self.mesh.shape["model"], bucket)]
        self._sharded_plans = {}
        for w in self._windows():
            sp = plan_mod.build_sharded_plan(
                self.forest, self.cost_model, D, stride,
                self.num_lanes, self.max_q, self.max_kv_per_task,
                req_rows=req_rows, window=w, truncate=truncate,
                num_rows=bucket)
            self._sharded_plans[w] = sp
            shard_pr = [self._backend.prepare(p) for p in sp.shards]
            pr = jax.tree.map(lambda *xs: jnp.stack(xs), *shard_pr)
            prepared.append(pr)
            sig.append((w,) + tuple(tuple(a.shape)
                                    for a in jax.tree.leaves(pr)))
        self._fused_prepared = tuple(prepared)

        # sparse-merge bookkeeping: which rows must cross the wire, and
        # which shards hold a shard-local contribution to them (all
        # windows OR together — one contrib vector serves every layer)
        row_sh = np.zeros((D, bucket), bool)
        merge_mask = np.zeros(bucket, bool)
        rep_set: Set[int] = set()
        for sp in self._sharded_plans.values():
            if sp.row_shards is not None:
                row_sh |= sp.row_shards
            if sp.merge_rows is not None:
                merge_mask |= sp.merge_rows
            rep_set |= sp.replicated or set()

        valid = np.zeros(bucket, bool)
        valid[:B] = True
        q_pos0 = np.full(bucket, -1, np.int32)
        tail_page = np.full((D, bucket), self.pool.local_trash, np.int32)
        tail_owner = np.zeros((D, bucket), bool)
        tail_base = np.zeros(bucket, np.int32)
        tail_off0 = np.zeros(bucket, np.int32)
        for i, r in enumerate(rows):
            q_pos0[i] = self.forest.context_len(r) - 1
            leaf = self.forest.nodes[self.forest.leaf_of[r]]
            tp = (leaf.length - 1) // ps
            reps = leaf.meta.get("replicas")
            if reps is not None:
                # every shard writes the row's new KV into its OWN
                # replica tail page, keeping replicas bitwise in sync;
                # ownership (whose tail partial counts) depends on
                # whether the row merges: fully-replicated rows own
                # everywhere (identical results), merge rows own only on
                # the primary (one contribution on the wire)
                for sh in range(D):
                    g = reps[sh][tp]
                    tail_page[sh, i] = self.pool.local_of(g)
                if leaf.id in rep_set:
                    tail_owner[:, i] = True
                else:
                    sh = self.pool.shard_of(leaf.page_ids[tp])
                    tail_owner[sh, i] = True
                    row_sh[sh, i] = True
            else:
                g = leaf.page_ids[tp]
                sh = self.pool.shard_of(g)
                tail_page[sh, i] = self.pool.local_of(g)
                tail_owner[sh, i] = True
                row_sh[sh, i] = True
            tail_base[i] = leaf.start_pos + tp * ps
            tail_off0[i] = (leaf.length - 1) % ps

        # packed gather/scatter for the sparse subgroup merge: Bm is part
        # of the compiled signature (bucketed pow2; 0 drops the
        # collective), the mask VALUES are not — one program per shape
        mrows = np.nonzero(merge_mask)[0]
        Bm = plan_mod.bucket_pow2(len(mrows)) if len(mrows) else 0
        gather = np.zeros(Bm, np.int32)
        scatter = np.full(Bm, bucket, np.int32)    # pad -> drop
        gather[:len(mrows)] = mrows
        scatter[:len(mrows)] = mrows
        contrib = (row_sh[:, merge_mask].any(axis=1) if Bm
                   else np.zeros(D, bool))
        sig.append(("merge", Bm))
        self.bucket_signatures.add(tuple(sig))
        self._fused_base = sharded_step_fn_mod.ShardedStepBase(
            jnp.asarray(valid), jnp.asarray(q_pos0),
            jnp.asarray(tail_page), jnp.asarray(tail_base),
            jnp.asarray(tail_off0), jnp.asarray(tail_owner),
            jnp.asarray(gather), jnp.asarray(scatter),
            jnp.asarray(contrib))
        self._record_epoch_features(Bm)

    def _record_epoch_features(self, merge_bucket: int) -> None:
        """Per-step cost-model features of the new epoch, attached to
        every step_stats row until the next epoch (``recalibrate`` fits
        hardware coefficients against them).  Compute terms take the
        heaviest shard's totals over its parallel lanes — the same
        makespan proxy the scheduler optimises."""
        ps = self.page_size
        lanes = max(self.num_lanes, 1)
        n_attn_w = {w: 0 for w in self._windows()}
        for kind in self.layer_kinds:
            if kind.mixer in ("attn", "attn_local"):
                w = (self.cfg.sliding_window if kind.mixer == "attn_local"
                     else 0)
                n_attn_w[w] += 1
        hbm = steps = 0.0
        shard_hbm: List[float] = []
        shard_steps: List[float] = []
        for w, sp in self._sharded_plans.items():
            per_shard = [sum(self.cost_model.hbm_bytes(s.n_q, s.n)
                             for s in p.subtasks) for p in sp.shards]
            per_steps = [sum(max(1, -(-s.n // ps)) for s in p.subtasks)
                         for p in sp.shards]
            if not per_shard:
                continue
            if not shard_hbm:
                shard_hbm = [0.0] * len(per_shard)
                shard_steps = [0.0] * len(per_steps)
            for i, (b, g) in enumerate(zip(per_shard, per_steps)):
                shard_hbm[i] += n_attn_w[w] * b / lanes
                shard_steps[i] += n_attn_w[w] * g / lanes
            k = int(np.argmax(per_shard))
            hbm += n_attn_w[w] * per_shard[k] / lanes
            steps += n_attn_w[w] * per_steps[k] / lanes
        D = self.pool.num_shards
        rounds = (int(np.ceil(np.log2(D)))
                  if D > 1 and merge_bucket > 0 else 0)
        n_attn = sum(n_attn_w.values())
        wire = (merge_bucket * self.cfg.num_heads
                * (self.cfg.head_dim + 2) * 4)
        self._epoch_features = {
            "hbm_bytes": hbm, "grid_steps": steps,
            "merge_bytes": n_attn * rounds * wire,
            "merge_rounds": n_attn * rounds,
        }
        # per-shard vectors kept separately: attached to profiled rows
        # only (they would bloat every ordinary step row)
        self._epoch_shard_features = {
            "shard_hbm_bytes": shard_hbm,
            "shard_grid_steps": shard_steps,
        } if shard_hbm else {}

    def predicted_step_seconds(self, hw=None) -> float:
        """Model-predicted per-step attention + merge seconds for the
        current epoch on a real mesh: the heaviest shard's HBM/grid time
        plus the cross-shard merge wire/launch terms, under ``hw`` (by
        default the current, possibly :meth:`recalibrate`-fitted,
        hardware coefficients — pass a fixed :class:`HardwareSpec` when
        comparing across engines).  Excludes the dense
        (FFN/unembed/dispatch) base cost, which is
        device-count-independent — callers compare or offset it against
        a measured single-device step."""
        f = self._epoch_features
        if not f:
            return 0.0
        hw = hw or self.cost_model.hw
        return (f["hbm_bytes"] / hw.hbm_bw
                + f["grid_steps"] * hw.grid_step_overhead
                + f["merge_bytes"] / hw.ici_bw
                + f["merge_rounds"] * hw.launch_overhead)

    def _sync_mamba_state(self) -> None:
        """Scatter the batched device SSM state back into the per-request
        store (device slices — no host transfer)."""
        if self._mamba_carry is None or self._fused_rows is None:
            return
        conv_all, ssm_all = self._mamba_carry
        for li, j in enumerate(self._mamba_layer_js):
            st = self.mamba_state.setdefault(j, {})
            for i, r in enumerate(self._fused_rows):
                req = self.requests.get(r)
                if req is not None and req.state == RUNNING:
                    st[r] = (conv_all[li, i:i + 1], ssm_all[li, i:i + 1])

    def _gather_mamba_state(self, rows: List[int], bucket: int) -> None:
        """Stack per-request SSM state into per-layer batched device
        arrays for the new epoch (padded rows stay zero)."""
        js = self._mamba_layer_js
        cfg = self.cfg
        K, conv_dim = cfg.ssm_conv, cfg.d_inner + 2 * cfg.ssm_state
        conv = jnp.zeros((len(js), bucket, max(K - 1, 0), conv_dim),
                         jnp.float32)
        ssm = jnp.zeros((len(js), bucket, max(cfg.ssm_heads, 1),
                         max(cfg.ssm_head_dim, 1), max(cfg.ssm_state, 1)),
                        jnp.float32)
        for li, j in enumerate(js):
            st = self.mamba_state.get(j, {})
            conv = conv.at[li, :len(rows)].set(
                jnp.concatenate([st[r][0] for r in rows], 0))
            ssm = ssm.at[li, :len(rows)].set(
                jnp.concatenate([st[r][1] for r in rows], 0))
        if self._replicated_sharding is not None:
            conv = jax.device_put(conv, self._replicated_sharding)
            ssm = jax.device_put(ssm, self._replicated_sharding)
        self._mamba_carry = (conv, ssm)

    # ------------------------------------------------------------------ #
    # speculative tree-decoding phase (serving/speculation.py, DESIGN §10):
    # draft-propose -> tree-verify (one multi-query dispatch) ->
    # accept/commit (KV moves from draft pages to the leaf tail) ->
    # rollback (draft pages released)
    # ------------------------------------------------------------------ #
    def _rollback_drafts(self, rid: int) -> None:
        """Release a request's live draft tree: detach the virtual
        branch-head queries, prune the draft nodes leaf-first, and
        return their pages to the allocator.  Idempotent no-op when the
        request holds no drafts (the common non-speculative case)."""
        st = self._drafts.pop(rid, None)
        if st is None:
            return
        for virt in st.virts:
            if virt in self.forest.leaf_of:
                self.forest.detach_request(virt)
        for nid in reversed(st.nodes):      # children before parents
            if nid not in self.forest.nodes:
                continue
            pages = self.forest.prune_leaf(nid)
            if pages:
                self.pool.allocator.release(pages)

    def _grow_drafts(self, rows: List[int]) -> None:
        """Propose and materialise each running request's draft tree.

        Draft pages are allocated best-effort: speculation never evicts
        to make room (a wrong guess would have paid an eviction for
        nothing), it just drafts fewer nodes — committed-token progress
        is unaffected because verification degenerates to normal decode.
        """
        reserve = self.policy.reserve_pages
        for r in rows:
            req = self.requests[r]
            room = req.max_new - len(req.generated)
            if room <= 0:        # only the done-transition dispatch left
                continue
            branches = self.proposer.propose(req.seq, max_tokens=room)
            if not branches:
                continue
            leaf_id = self.forest.leaf_of[r]
            st = spec_mod.DraftState(r)
            stalled = False
            for chain in branches:
                parent = leaf_id
                for tok in chain:
                    if self.pool.num_free - reserve < 1:
                        stalled = True
                        break
                    node = self.forest.add_draft(parent, int(tok))
                    node.page_ids = self.pool.allocator.alloc(
                        1, hint=node.id)
                    virt = self._next_virt
                    self._next_virt -= 1
                    self.forest.attach_request(virt, node.id)
                    st.nodes.append(node.id)
                    st.virts.append(virt)
                    parent = node.id
                if stalled:
                    break
            if stalled:
                self.stats["spec_draft_stalls"] += 1
            if st.nodes:
                self._drafts[r] = st
                self.stats["spec_proposed"] += len(st.nodes)

    def _spec_layout(self, rows: List[int]):
        """Stack the verification queries: per request its committed-tail
        base query (the normal decode position) then one query per draft
        node, each with its token, absolute position, and the KV slot
        the dispatch writes that token's K/V into."""
        ps = self.page_size
        tokens: List[int] = []
        q_pos: List[int] = []
        w_page: List[int] = []
        w_off: List[int] = []
        req_rows: Dict[int, int] = {}
        head_rows: Dict[int, Dict[int, int]] = {}
        for r in rows:
            req = self.requests[r]
            leaf = self.forest.nodes[self.forest.leaf_of[r]]
            tp = (leaf.length - 1) // ps
            head_rows[r] = {leaf.id: len(tokens)}
            req_rows[r] = len(tokens)
            tokens.append(req.generated[-1])
            q_pos.append(leaf.end_pos - 1)
            w_page.append(leaf.page_ids[tp])
            w_off.append((leaf.length - 1) % ps)
            st = self._drafts.get(r)
            if st is None:
                continue
            for nid, virt in zip(st.nodes, st.virts):
                node = self.forest.nodes[nid]
                head_rows[r][nid] = len(tokens)
                req_rows[virt] = len(tokens)
                tokens.append(int(node.tokens[0]))
                q_pos.append(node.end_pos - 1)
                w_page.append(node.page_ids[0])
                w_off.append(0)
        return tokens, q_pos, w_page, w_off, req_rows, head_rows

    def _decode_phase_spec(self) -> Dict[int, Optional[int]]:
        rows0 = self._active_rows()
        if not rows0:
            return {}
        tm = self.telemetry
        self._append_pending(rows0)        # host ints: spec never defers
        rows = self._active_rows()
        if not rows:
            return {}
        with telemetry_mod.Span("codec.spec_propose", tm,
                                "spec_propose") as sp:
            self._grow_drafts(rows)
            sp.args = {"rows": len(rows),
                       "drafts": sum(len(st.nodes)
                                     for st in self._drafts.values())}
        # injected NaN: poison a committed KV slot of the target's leaf
        # (as in the fused path) so every verify row of that request —
        # base query and draft heads — reads it through the verify plan
        if self.injector is not None:
            spec = self.injector.take("nan_logits")
            if spec is not None:
                target = spec.rid if spec.rid in rows else rows[0]
                leaf = self.forest.nodes[self.forest.leaf_of[target]]
                owners = [q for q in leaf.requests if q >= 0]
                kids = [c for c in leaf.children
                        if not self.forest.nodes[c].meta.get("draft")]
                if leaf.length >= 2 and owners == [target] and not kids:
                    slot = leaf.length - 2
                    page = leaf.page_ids[slot // self.page_size]
                    off = slot % self.page_size
                    self.pool.k = self.pool.k.at[:, page, off].set(
                        jnp.nan)
                    self._nan_dirty.append((page, off))
                    self.stats["faults_injected"] += 1
                else:
                    self.injector.requeue(spec)
        tokens, q_pos, w_page, w_off, req_rows, head_rows = \
            self._spec_layout(rows)
        with telemetry_mod.Span("codec.plan_build") as sp:
            plans = {w: plan_mod.build_verify_plan(
                self.forest, self.cost_model, req_rows, self.num_lanes,
                self.max_q, self.max_kv_per_task, window=w,
                kind=self._backend.plan_kind) for w in self._windows()}
        self.stats["replans"] += 1
        self.stats["plan_time"] += sp.seconds
        with telemetry_mod.Span("codec.spec_verify", tm, "spec_verify",
                                args={"queries": len(tokens)}) as sv:
            verify = (self._spec_verify_fused
                      if self._spec_step_fn is not None
                      else self._spec_verify_eager)
            toks, ok = verify(tokens, q_pos, w_page, w_off, plans)
        if self.nan_guard:
            # quarantine before commit: a poisoned request's drafts roll
            # back with it and nothing enters its committed stream
            for r in list(rows):
                if not bool(ok[req_rows[r]]):
                    self.stats["nan_rows"] += 1
                    self._fail_request(r, "nan_logits", flush=False)
        with telemetry_mod.Span("codec.spec_accept", tm,
                                "spec_accept") as sa:
            out = self._spec_commit(rows, toks, head_rows)
            sa.args = {"accepted": sum(1 for v in out.values()
                                       if v is not None)}
        self.stats["steps"] += 1
        self.stats["spec_steps"] += 1
        self._decode_timing.update(dispatch_time=sv.seconds,
                                   compute_time=sa.t1 - sv.t1)
        self.stats["decode_dispatch_time"] += sv.seconds
        return out

    def _spec_verify_eager(self, tokens, q_pos, w_page, w_off,
                           plans) -> np.ndarray:
        """Eager multi-query verification: per-layer loop, the backend's
        ``partials`` over the verify plan (which covers the whole forest,
        so no tail/POR merge), greedy argmax on the host."""
        cfg = self.cfg
        B = len(tokens)
        qp = jnp.asarray(np.asarray(q_pos, np.int32))
        pages = np.asarray(w_page)
        offs = np.asarray(w_off)
        prepared = {w: self._backend.prepare(p) for w, p in plans.items()}
        x = T._embed(self.params, cfg, jnp.asarray(tokens)[None].T,
                     qp[:, None])                            # (B,1,d)
        for j, kind in enumerate(self.layer_kinds):
            p = layer_params(cfg, self.params, j)
            h = L.apply_norm(p["ln"], x, cfg)
            if kind.mixer in ("attn", "attn_local"):
                la = self.attn_layer_idx[j]
                window = (cfg.sliding_window if kind.mixer == "attn_local"
                          else 0)
                q, k_new, v_new = L.attn_project(p["attn"], cfg, h,
                                                 qp[:, None])
                self.pool.write_tokens(la, pages, offs,
                                       k_new[:, 0], v_new[:, 0])
                k_pool, v_pool = self.pool.layer_pools(la)
                o, _, _ = self._backend.partials(
                    q[:, 0], k_pool, v_pool, plans[window],
                    prepared[window], window=window)
                y = L.dense(p["attn"]["wo"],
                            o.astype(q.dtype).reshape(
                                B, 1, cfg.num_heads * cfg.head_dim))
                x = x + y
            x, _ = L.apply_ffn_block(p, cfg, kind.ffn, x)
        logits = T._unembed(self.params, cfg, x)[:, 0]       # (B, V)
        return (np.asarray(jnp.argmax(logits, -1)),
                np.asarray(jnp.isfinite(logits).all(-1)))

    def _spec_verify_fused(self, tokens, q_pos, w_page, w_off,
                           plans) -> np.ndarray:
        """Fused verification: ONE jitted, donated, bucketed dispatch
        scores every branch head (serving/step_fn.make_spec_step_fn);
        the host syncs once per verify step for the acceptance walk."""
        B = len(tokens)
        bucket = plan_mod.bucket_pow2(B)
        prepared = []
        sig: List = [("spec", bucket)]
        for w in self._windows():
            p = plan_mod.bucket_plan(plans[w], bucket)
            pr = self._backend.prepare(p)
            prepared.append(pr)
            sig.append((w,) + tuple(tuple(a.shape)
                                    for a in jax.tree.leaves(pr)))
        self.bucket_signatures.add(tuple(sig))
        tok = np.zeros(bucket, np.int32)
        tok[:B] = tokens
        qp = np.full(bucket, -1, np.int32)
        qp[:B] = q_pos
        wp = np.full(bucket, self.pool.trash_page, np.int32)
        wp[:B] = w_page
        wo = np.zeros(bucket, np.int32)
        wo[:B] = w_off
        state = step_fn_mod.SpecState(self.pool.k, self.pool.v)
        toks_dev, ok_dev, state = self._spec_step_fn(
            self.params, state, jnp.asarray(tok), jnp.asarray(qp),
            jnp.asarray(wp), jnp.asarray(wo), tuple(prepared))
        self.pool.k, self.pool.v = state.pool_k, state.pool_v
        self.stats["fused_calls"] += 1
        return np.asarray(toks_dev)[:B], np.asarray(ok_dev)[:B]

    def _spec_commit(self, rows: List[int], toks: np.ndarray,
                     head_rows) -> Dict[int, Optional[int]]:
        """Greedy accept/commit/rollback for every request.

        Per request: walk the scored draft tree (``speculation.
        accept_walk``), roll the whole tree back (freeing its pages),
        then append the accepted tokens to the committed leaf — moving
        each one's KV from its draft page to the leaf's tail slot in a
        single aliasing-safe ``copy_slots`` gather/scatter — and carry
        the correction/bonus token as the next ``pending``.  The
        committed forest layout after a speculative step is exactly
        what non-speculative decode would have produced, so plans,
        eviction, and the differential harness see nothing new.
        """
        ps = self.page_size
        out: Dict[int, Optional[int]] = {}
        for r in rows:
            req = self.requests[r]
            if req.state != RUNNING:   # preempted committing earlier rows
                continue
            leaf_id = self.forest.leaf_of[r]
            rowmap = head_rows[r]
            room = req.max_new - len(req.generated)
            accepted, final_tok = spec_mod.accept_walk(
                self.forest, leaf_id,
                lambda nid: toks[rowmap[nid]], room)
            # source KV slots + token values, recorded before rollback
            moves = [(self.forest.nodes[nid].page_ids[0],
                      int(self.forest.nodes[nid].tokens[0]))
                     for nid in accepted]
            self._rollback_drafts(r)
            copies = []
            for src_page, tok in moves:
                self.forest.append_token(r, tok)
                req.generated.append(tok)
                leaf = self._grow_leaf_tail(r)
                # exclude={r} forbids self-preemption, so r must still be
                # running; a silent skip here would leave the appended
                # tokens without their KV copy
                assert req.state == RUNNING, (r, req.state)
                tp = (leaf.length - 1) // ps
                copies.append((src_page, 0, leaf.page_ids[tp],
                               (leaf.length - 1) % ps))
            if copies:
                src_p, src_o, dst_p, dst_o = map(np.asarray, zip(*copies))
                self.pool.copy_slots(src_p, src_o, dst_p, dst_o)
                self.stats["spec_accepted"] += len(copies)
                if self.telemetry is not None:
                    self._note_token(req)
            req.computed_hwm = max(req.computed_hwm,
                                   self.forest.context_len(r))
            if len(req.generated) >= req.max_new:
                req.state = DONE
                req.pending = None
                out[r] = req.generated[-1]
            else:
                req.pending = final_tok
                out[r] = final_tok
        return out

    # ------------------------------------------------------------------ #
    def run(self, max_steps: int = 64,
            on_step=None) -> Dict[int, List[int]]:
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()
            if on_step is not None:
                on_step(self)
        self.flush_tokens()
        self._stream_ready()
        self._notify_done()
        return {r: req.generated for r, req in self.requests.items()}

    def release(self, rid: int) -> None:
        self.flush_tokens()
        req = self.requests.pop(rid)
        self.admission.remove(rid)      # queue entry + EDF bookkeeping
        if req.state == WAITING:
            for nid in req.pinned:
                node = self.forest.nodes.get(nid)
                if node is not None:
                    node.meta["pins"] = node.meta.get("pins", 0) - 1
                    self._maybe_free_node(node)
            req.pinned = []
            return
        if rid in self._prefilling:
            self._prefilling.remove(rid)
        if rid in self.forest.leaf_of:
            self._release_kv(rid)
        self._pending_ref.pop(rid, None)

    # ------------------------------------------------------------------ #
    # request lifecycle control + fault tolerance (DESIGN.md §12)
    # ------------------------------------------------------------------ #
    def cancel(self, rid: int) -> bool:
        """Cancel a request in any pre-terminal state.

        The KV it holds is released (waiting pins unwound, live drafts
        rolled back), its stream is closed via ``on_done(rid,
        "cancelled")``, and already-delivered tokens stand.  Returns
        ``False`` when the request is unknown or already terminal."""
        req = self.requests.get(rid)
        if req is None or req.state in TERMINAL:
            return False
        self.flush_tokens()    # deliverable tokens land before closing
        self._finish(req, CANCELLED, "cancelled")
        return True

    def _finish(self, req: Request, state: str, reason: str) -> None:
        """Centralised terminal transition for the failure states.

        Unwinds whatever stage the request is in — waiting (queue entry
        + pins), prefilling, or running (forest membership, drafts,
        deferred-token refs) — releases its KV, and closes the stream.
        ``FAILED`` force-frees the private leaf (possibly-poisoned KV
        must not become cache content)."""
        if req.state in TERMINAL and req.kv_freed:
            return
        rid = req.rid
        if req.state == WAITING:
            self.admission.remove(rid)
            for nid in req.pinned:
                node = self.forest.nodes.get(nid)
                if node is not None:
                    node.meta["pins"] = node.meta.get("pins", 0) - 1
                    self._maybe_free_node(node)
            req.pinned = []
        else:
            if rid in self._prefilling:
                self._prefilling.remove(rid)
            if rid in self.forest.leaf_of:
                self._release_kv(rid, force_leaf=state == FAILED)
            self._pending_ref.pop(rid, None)
            # the fused epoch's plan references the departed row (and
            # possibly its freed pages): force a rebuild
            self._plan_dirty = True
        req.pending = None
        req.kv_freed = True
        req.state = state
        req.finish_reason = reason
        self.stats[{CANCELLED: "cancelled", TIMED_OUT: "timed_out",
                    FAILED: "failed"}[state]] += 1
        self._fire_on_done(req)

    def _fail_request(self, rid: int, reason: str,
                      flush: bool = True) -> None:
        """Quarantine one request as FAILED without poisoning the batch."""
        req = self.requests.get(rid)
        if req is None or (req.state in TERMINAL and req.kv_freed):
            return
        if flush:
            self.flush_tokens()
        if reason == "nan_logits" and self._nan_dirty:
            # scrub injected NaN slots before the pages return to the
            # free list: a future tenant must never read them
            for page, off in self._nan_dirty:
                self.pool.k = self.pool.k.at[:, page, off].set(0.0)
            self._nan_dirty = []
        self._finish(req, FAILED, reason)

    def _fire_on_done(self, req: Request) -> None:
        """Close the stream exactly once; isolate a raising callback."""
        if req.notified:
            return
        req.notified = True
        self.admission.remove(req.rid)   # drop EDF deadline bookkeeping
        tm = self.telemetry
        if tm is not None:
            reason = req.finish_reason or "done"
            tm.end_all(req.rid)
            tm.instant(reason, track=req.rid,
                       args={"tokens": len(req.generated)})
            tm.metrics.counter("tokens_generated").inc(len(req.generated))
            if reason == "done":
                tm.metrics.counter("requests_done").inc()
            if req.first_tok_t is not None:
                tm.observe("ttft_s", req.first_tok_t - req.submit_t)
                tm.observe("e2e_s",
                           (req.last_tok_t or req.first_tok_t)
                           - req.submit_t)
                if req.last_tok_t is not None and len(req.generated) > 1:
                    tm.observe("tpot_s",
                               (req.last_tok_t - req.first_tok_t)
                               / (len(req.generated) - 1))
        try:
            if self.injector is not None and req.on_done is not None:
                spec = self.injector.take("callback", rid=req.rid)
                if spec is not None:
                    self.stats["faults_injected"] += 1
                    raise InjectedFault(
                        spec, f"injected on_done failure for request "
                              f"{req.rid}")
            if req.on_done is not None:
                req.on_done(req.rid, req.finish_reason or "done")
        except Exception:
            self.stats["callback_errors"] += 1
            if req.state == DONE:
                # the only visible casualty is this request's status
                if req.rid in self.forest.leaf_of:
                    self._release_kv(req.rid)
                    self._plan_dirty = True
                req.kv_freed = True
                req.state = FAILED
                req.finish_reason = "callback_error"
                self.stats["failed"] += 1

    def _notify_done(self) -> None:
        """Fire ``on_done`` for normally-completed requests whose stream
        has fully drained (failure states notify inside ``_finish``)."""
        with telemetry_mod.Span("codec.stream"):
            for req in list(self.requests.values()):
                if req.state != DONE or req.notified:
                    continue
                if req.on_token is not None and req.emitted < len(
                        req.generated):
                    continue    # tokens still deferred: next boundary
                req.finish_reason = req.finish_reason or "done"
                self._fire_on_done(req)

    def _enforce_deadlines(self) -> None:
        """Step-boundary deadline sweep over every pre-terminal request
        (the waiting queue included): expired requests transition to
        TIMED_OUT with their KV released and their stream closed."""
        now = self.clock()
        for req in list(self.requests.values()):
            if req.state in TERMINAL:
                continue
            if req.deadline is not None and now >= req.deadline:
                self.flush_tokens()
                self._finish(req, TIMED_OUT, "deadline")
            elif (req.state == WAITING and req.queue_deadline is not None
                  and now >= req.queue_deadline):
                self._finish(req, TIMED_OUT, "queue_timeout")

    def check(self) -> None:
        """Serving-time invariant self-check (raises
        :class:`~repro.serving.faults.EngineInvariantError`).

        Consolidates the allocator's structural ``check()`` with the
        engine-level cross-structure invariants: every allocated page is
        owned by exactly one forest node (replicas and draft pages
        included), pin refcounts equal the waiting holders' pin lists,
        ``leaf_of`` is coherent with request states, deferred-token refs
        point at live deferred rows, and cache residency fits the pool.
        Run after every dispatch recovery and every ``check_every``
        steps; cheap enough for tests to call after each scenario."""
        self.stats["invariant_checks"] += 1
        failures: List[str] = []
        try:
            self.forest.validate()
        except AssertionError as e:
            failures.append(f"forest: {e}")
        try:
            self.pool.allocator.check()
        except AssertionError as e:
            failures.append(f"allocator: {e}")
        owned: Dict[int, int] = {}
        for node in self.forest.nodes.values():
            if node.id == tree_mod.ROOT_ID:
                continue
            reps = node.meta.get("replicas")
            pages = ([p for run in reps.values() for p in run]
                     if reps is not None else node.page_ids)
            for p in pages:
                owned[p] = owned.get(p, 0) + 1
        for p, n in owned.items():
            if n != 1:
                failures.append(f"page {p} owned by {n} nodes")
        used = self.pool.allocator.used_page_ids()
        leaked = sorted(set(used) - set(owned))
        dangling = sorted(set(owned) - set(used))
        if leaked:
            failures.append(
                f"{len(leaked)} leaked page(s) (allocated, owned by no "
                f"node): {leaked[:8]}")
        if dangling:
            failures.append(
                f"{len(dangling)} dangling page(s) (node-owned, not "
                f"allocated): {dangling[:8]}")
        pin_count: Dict[int, int] = {}
        for req in self.requests.values():
            for nid in req.pinned:
                pin_count[nid] = pin_count.get(nid, 0) + 1
        for node in self.forest.nodes.values():
            pins = node.meta.get("pins", 0)
            if pins != pin_count.get(node.id, 0):
                failures.append(
                    f"node {node.id} pins={pins} but "
                    f"{pin_count.get(node.id, 0)} holder(s) list it")
        for rid, req in self.requests.items():
            if (req.state in (PREFILL, RUNNING)
                    and rid not in self.forest.leaf_of):
                failures.append(f"live request {rid} has no forest leaf")
            if req.state == WAITING and rid in self.forest.leaf_of:
                failures.append(
                    f"waiting request {rid} still in the forest")
            if (req.state in TERMINAL and req.kv_freed
                    and rid in self.forest.leaf_of):
                failures.append(
                    f"finished request {rid} still holds forest KV")
        draft_virts = {v for st in self._drafts.values()
                       for v in st.virts}
        for rid in self.forest.leaf_of:
            if rid >= 0 and rid not in self.requests:
                failures.append(
                    f"forest request {rid} unknown to the engine")
            if rid < 0 and rid not in draft_virts:
                failures.append(
                    f"virtual query {rid} without a draft tree")
        for rid in self._pending_ref:
            req = self.requests.get(rid)
            if req is None or req.pending is not PENDING_DEVICE:
                failures.append(
                    f"dangling deferred-token ref for request {rid}")
        if self.cache is not None:
            resident = self.cache.resident_pages()
            total_used = sum(used.values()) if used else 0
            if resident > total_used:
                failures.append(
                    f"cache claims {resident} resident pages but only "
                    f"{total_used} are allocated")
        if failures:
            raise EngineInvariantError(failures)

    def shutdown(self) -> Dict[str, int]:
        """Graceful teardown: cancel all outstanding work, drop finished
        and cached KV, self-check, and return a leak summary
        (``used_pages`` must be 0 after a clean shutdown)."""
        for rid in sorted(self.requests):
            if self.requests[rid].state not in TERMINAL:
                self.cancel(rid)
        self.flush_tokens()
        self._stream_ready()
        self._notify_done()
        for rid, req in sorted(self.requests.items()):
            if rid in self.forest.leaf_of:    # DONE, KV still resident
                self._release_kv(rid)
                req.kv_freed = True
        if self.cache is not None:
            self._evict_cached(self.pool.num_pages)
        self.check()
        return {"used_pages": self.pool.allocator.num_used,
                "requests": len(self.requests)}
