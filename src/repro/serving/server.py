"""Request-lifecycle serving: one step-driven core under both schedulers.

``Server`` is the serving facade over ``ModuleBatchingEngine`` +
``ParamStore``: requests are submitted (``submit(Request) ->
RequestHandle``), become admissible at their ``arrival_s`` offset on a
virtual clock keyed off wall time, and are driven by ``step()`` — ONE
module-batched decode tick that admits due arrivals, decodes every live
slot, samples each slot under its own ``SamplingParams``, and
evicts/recycles finished sequences.  ``run()`` loops ``step()`` (sleeping
through idle gaps until the next arrival) and returns the ``ServeReport``.

When the engine's fused decode path is eligible and no admission or
eviction can fall due mid-chunk, ``step()`` batches up to
``decode_chunk`` ticks into ONE fused device dispatch
(``engine.decode_chunk``) — clamped to the shortest remaining decode so
every finish event still lands at a chunk boundary; tokens, timestamps
and the waste accounting are tick-identical to per-tick stepping (see
``_chunk_T``).

The two scheduler modes are thin *admission policies* over that single
core — the prefill/decode/EOS/latency bookkeeping lives once:

* ``static`` — the paper's offline protocol (§5.1): requests are admitted
  in waves, a new wave only when the previous one has fully drained; every
  wave slot keeps stepping until the wave's slowest member finishes
  (early finishers are counted in ``wasted_slot_steps``), and each wave's
  raw token matrix is recorded as a ``BatchResult``.
* ``continuous`` — in-flight batching (vLLM-style): a finished sequence's
  slot, KV rows and SSM state are evicted immediately and the freed slot
  is recycled by prefilling the next due request into it; with
  ``ServeConfig.hw`` set, admission is additionally gated by the Eq. 2
  host KV budget (the queue head waits, FIFO, counted in
  ``admission_deferrals``).

Both modes produce identical tokens per request when the plan's expert
capacity ``b_e`` admits every routed token (capacity drops depend on batch
composition, which the modes schedule differently), and the sampling
determinism contract (see ``serving.sampling``) makes that hold for
seeded sampled requests too.

Per-request latency metrics are measured on the virtual clock:
``queue_wait_s`` (arrival -> admission), ``ttft_s`` (arrival -> first
token, which the admission prefill produces), and ``tpot_s`` (mean
per-token latency after the first).
"""
from __future__ import annotations

import contextlib
import heapq
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults
from repro.analysis import runtime as sanitizer
from repro.analysis.markers import hot_path
from repro.analysis.spans import span
from repro.configs.base import ModelConfig
from repro.core import workload as W
from repro.core.dag_builder import Plan
from repro.core.hardware import HardwareProfile
from repro.serving.sampling import BatchSampler, SamplingParams
from repro.serving.weights import ParamStore


# ---------------------------------------------------------------------------
# Requests, configs, results
# ---------------------------------------------------------------------------
@dataclass
class Request:
    prompt: np.ndarray            # (S,) int32
    decode_len: int
    arrival_s: float = 0.0        # admissible-from offset on the virtual clock
    sampling: Optional[SamplingParams] = None   # None = greedy


@dataclass(frozen=True)
class ServeConfig:
    """Scheduling-side knobs, frozen (was: the ``serve_dataset`` kwarg
    sprawl).  ``decode_len`` is the fallback for requests whose own field
    is zero/None; ``hw`` enables Eq. 2 memory-gated admission in the
    continuous scheduler.

    KV-cache knobs (the ``serving.cache`` tier): ``kv_page_tokens > 0``
    pages the cache; ``device_kv_gb`` caps the device page pool (the
    remainder streams from the host tier); ``prefix_cache`` admits repeated
    prompt prefixes by copying cached page rows instead of recomputing
    prefill (attention-only models without a sliding window).

    ``from_plan`` builds a config sized by the planner up front —
    ``max_batch``/``max_seq`` come from ``planner.search_decode`` instead
    of the first step's submitted queue.
    """

    scheduler: str = "static"
    decode_len: int = 32
    max_seq: Optional[int] = None
    max_prompt_len: Optional[int] = None
    pad_id: int = 0
    eos_id: Optional[int] = None
    grouped_prefill: bool = True
    hw: Optional[HardwareProfile] = None
    decode_chunk: Optional[int] = None   # fused chunk T cap (None = plan's);
    #                                      1 disables multi-token stepping
    kv_page_tokens: int = 0              # page the KV cache (0 = contiguous)
    device_kv_gb: Optional[float] = None  # device page-pool cap (None = all)
    prefix_cache: bool = False           # reuse shared prompt prefixes
    max_batch: Optional[int] = None      # engine slots (None = sized at the
    #                                      first step from the submitted queue)
    plan: Optional[Plan] = None          # planner-produced Plan (from_plan);
    #                                      used when Server gets plan=None
    replan_skew: Optional[float] = None  # online capacity re-plan: re-derive
    #   b_e from the measured expert-load histogram whenever the hottest
    #   expert's share drifts by more than this (absolute share delta);
    #   None disables re-planning
    replan_drop_target: float = 0.01     # expected drop-rate bound the
    #                                      re-planned capacity is sized for
    sctx: Optional[object] = None        # sharding.specs.ShardCtx with a mesh
    #   + model axis: the engine runs the MoE stage as collective dispatch
    #   (repro.distributed.ep_engine); None = single-device (byte-identical
    #   to the pre-mesh paths)
    ep_chunks: int = 1                   # pipeline chunks the a2a MoE stage
    #   splits the accumulated batch into (chunk k+1's all-to-all overlaps
    #   chunk k's expert FFN); 1 = serial dispatch
    device: Optional[object] = None      # jax Device the engine, its KV and
    #   its resident weights live on (None = JAX's default device); with
    #   sctx, the home device of everything but the sharded expert stacks.
    #   ReplicaServer gives each replica its own
    faults: Optional[object] = None      # fault-injection schedule: a
    #   repro.faults FaultPlan / FaultSpec / spec string ("seed=0,
    #   transfer=0.1,..."); None = unarmed (the ambient REPRO_FAULTS plan,
    #   if any, still applies).  Armed around every step, so the stream /
    #   page / preemption seams consult it; recovery is counted in the
    #   report (transfer_retries, preemptions, ...)

    def __post_init__(self) -> None:
        assert self.scheduler in ("static", "continuous"), self.scheduler
        assert self.kv_page_tokens >= 0, self.kv_page_tokens
        if self.prefix_cache:
            assert self.kv_page_tokens > 0, (
                "prefix_cache requires paging (kv_page_tokens > 0)"
            )
        if self.max_batch is not None:
            assert self.max_batch >= 1, self.max_batch

    @classmethod
    def from_plan(
        cls,
        cfg: ModelConfig,
        hw: HardwareProfile,
        ctx: int = 512,
        scheduler: str = "continuous",
        B: Optional[int] = None,
        **overrides,
    ) -> "ServeConfig":
        """Size the serving config from the planner: runs
        ``planner.search_decode(cfg, hw, ctx)`` with ω pinned to 0, as
        ``launch.serve.plan_serving`` pins it (the engine attends every row
        on its device and refuses ω > 0), and pins ``max_batch`` to
        the plan's B, ``max_seq`` to ``ctx``, and ``hw`` for Eq. 2 gated
        admission — so the server allocates its engine up front instead of
        from whatever happens to be queued at the first step.  ``B`` caps
        the searched batch (Eq. 2 makes the host limit of a smoke-scale
        config astronomical — cap it to what the workload and this
        machine's memory actually support).  Keyword overrides win over
        the derived fields; the Plan rides along in ``.plan`` (pass
        ``Server(cfg, params, plan=None, serve=...)``)."""
        from repro.core.planner import search_decode

        plan = search_decode(cfg, hw, ctx, B=B, scheduler=scheduler,
                             use_cpu_attention=False,
                             decode_len=overrides.get("decode_len")).plan
        kw = dict(scheduler=scheduler, max_seq=ctx, max_batch=plan.B,
                  hw=hw, plan=plan)
        kw.update(overrides)
        return cls(**kw)


@dataclass(frozen=True)
class StreamConfig:
    """Weight-residency knobs for the ``ParamStore`` the server builds
    (ignored when a pre-built ``store`` is passed)."""

    stream_weights: bool = False
    resident_bytes: Optional[float] = None
    prefetch: bool = True
    predict_topk: Optional[int] = None   # per-expert predictive streaming
    #   (None = follow the plan's predict_topk; 0 forces whole-stack)
    lru_bytes: Optional[float] = None    # hot-expert device LRU budget
    #   (None = the residency plan's spare bytes)


@dataclass
class BatchResult:
    tokens: np.ndarray            # (B, decode_len) raw batch tokens (static)
    prefill_s: float
    decode_s: float
    expert_tokens_dropped: int = 0   # routed copies over the b_e capacity


@dataclass
class RequestResult:
    index: int                    # position in the input request list
    tokens: np.ndarray            # (n,) generated tokens (<= decode_len; EOS cut)
    latency_s: float              # admission -> last token (incl. its prefill)
    decode_steps: int             # decode steps while this request was live
    arrival_s: float = 0.0        # admissible-from offset (virtual clock)
    queue_wait_s: float = 0.0     # arrival -> admission
    ttft_s: float = 0.0           # arrival -> first token
    tpot_s: float = 0.0           # mean per-token latency after the first


@dataclass
class ServeReport:
    results: List[BatchResult] = field(default_factory=list)
    request_results: List[RequestResult] = field(default_factory=list)
    scheduler: str = "static"
    prefill_s: float = 0.0
    decode_s: float = 0.0
    decode_slot_steps: int = 0    # decode steps x batch slots executed
    wasted_slot_steps: int = 0    # slot-steps spent on finished/empty slots
    decode_ctx_tokens: int = 0    # cached positions the live decode rows
    #   attended in latent-attention (MLA) layers: per live slot-step, its
    #   position + 1, summed over the MLA layers — the context the absorbed
    #   decode kernel needs, whatever span the cache holds
    weight_htod_bytes: int = 0    # streamed weight bytes copied host->device
    prefetch_wait_s: float = 0.0  # stall waiting on weight transfers
    admission_deferrals: int = 0  # admissions blocked by the Eq. 2 KV budget
    kv_htod_bytes: int = 0        # streamed KV-page bytes copied host->device
    kv_dtoh_bytes: int = 0        # KV bytes spilled device->host
    prefix_hits: int = 0          # admissions served from the prefix cache
    prefix_misses: int = 0        # eligible admissions that prefilled cold
    prefill_tokens: int = 0       # token-positions actually computed in prefill
    #   (full prompts on a miss, suffix only on a prefix hit — the gap vs
    #   sum(len(prompt)) is the prefill work the prefix cache skipped)
    prefill_expert_rows: int = 0    # rows the prefill expert kernel computed
    prefill_routed_copies: int = 0  # routed copies of real prompt tokens
    #   in them (1 - copies/rows is prefill's expert padding)
    decode_expert_rows: int = 0   # rows the ragged decode expert kernel
    #   computed (used tiles x tile rows), summed over MoE layers and
    #   ticks; over decode_slot_steps x top_k, how well it packs
    _expert_dropped: int = 0      # drops counted outside BatchResults
    # predictive per-expert streaming + imbalance accounting (grouped path)
    expert_dropped_by_layer: Optional[np.ndarray] = None  # (n_moe,) drops
    expert_load: Optional[np.ndarray] = None  # (n_moe, E) routed-copy hist
    expert_pred_hits: int = 0     # expert was staged by the l+1 prediction
    expert_pred_misses: int = 0   # demand-fetched (mispredicted/cold) experts
    expert_lru_hits: int = 0      # served from the hot-expert device LRU
    capacity_replans: int = 0     # online b_e re-plans on measured skew drift
    a2a_bytes: int = 0            # interconnect bytes the mesh MoE stage
    #                               exchanged (a2a dispatch + return)
    collective_dispatches: int = 0  # mesh MoE stage launches (a2a/psum)
    # fault-recovery accounting (repro.faults): every recovery is counted
    # so fault handling is observable, never silent
    transfer_retries: int = 0     # transient stream fetches recovered by retry
    transfer_timeouts: int = 0    # watchdog-expired waits recovered by re-fetch
    preemptions: int = 0          # running requests evicted to host checkpoints
    resumes: int = 0              # checkpoints re-admitted (zero prefill relaunch)
    degrade_deferrals: int = 0    # admissions deferred under page-alloc pressure
    page_demotions: int = 0       # device page frames demoted to the host tier
    chunk_shrinks: int = 0        # decode-chunk cap halvings under pressure
    failovers: int = 0            # dead replicas failed over (ReplicaServer)
    requeued_requests: int = 0    # requests requeued onto surviving replicas

    @property
    def total_s(self) -> float:
        return self.prefill_s + self.decode_s

    @property
    def a2a_gb(self) -> float:
        """Expert-parallel all-to-all traffic in GB (0 off-mesh)."""
        return self.a2a_bytes / 1e9

    @property
    def htod_gb(self) -> float:
        """Streamed weight traffic in GB (0 when everything is resident)."""
        return self.weight_htod_bytes / 1e9

    @property
    def kv_htod_gb(self) -> float:
        """Streamed KV-page traffic in GB (0 without a host tier)."""
        return self.kv_htod_bytes / 1e9

    @property
    def kv_dtoh_gb(self) -> float:
        return self.kv_dtoh_bytes / 1e9

    @property
    def prefix_hit_rate(self) -> float:
        n = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / n if n else 0.0

    @property
    def decode_tokens(self) -> int:
        """Valid generated tokens (per-request decode_len / EOS honored)."""
        return sum(r.tokens.size for r in self.request_results)

    @property
    def expert_tokens_dropped(self) -> int:
        return self._expert_dropped + sum(
            r.expert_tokens_dropped for r in self.results
        )

    @property
    def routing_skew(self) -> float:
        """Hottest expert's measured share of routed copies (aggregated
        over MoE layers), as a multiple of the balanced share ``1/E`` —
        1.0 is perfectly balanced, E is fully collapsed routing.  0.0
        when no routed copies were measured (dense model / loop path)."""
        if self.expert_load is None:
            return 0.0
        per_expert = self.expert_load.sum(axis=0)
        total = per_expert.sum()
        if total <= 0:
            return 0.0
        return float(per_expert.max() / total * per_expert.size)

    @property
    def pred_hit_rate(self) -> float:
        """Fraction of decode-stage expert fetches the l+1 prediction (or
        the LRU) had already paid for — the htod latency actually hidden."""
        n = self.expert_pred_hits + self.expert_pred_misses
        return self.expert_pred_hits / n if n else 0.0

    @property
    def lru_hit_rate(self) -> float:
        """Fraction of decode-stage expert uses served from the hot-expert
        LRU (no copy at all), over all uses."""
        n = (self.expert_pred_hits + self.expert_pred_misses
             + self.expert_lru_hits)
        return self.expert_lru_hits / n if n else 0.0

    @property
    def decode_throughput(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s > 0 else 0.0

    @property
    def occupancy(self) -> float:
        """Fraction of executed decode slot-steps that produced live tokens."""
        if self.decode_slot_steps == 0:
            return 1.0
        return 1.0 - self.wasted_slot_steps / self.decode_slot_steps

    @property
    def mean_latency_s(self) -> float:
        rr = self.request_results
        return sum(r.latency_s for r in rr) / len(rr) if rr else 0.0

    @property
    def mean_queue_wait_s(self) -> float:
        rr = self.request_results
        return sum(r.queue_wait_s for r in rr) / len(rr) if rr else 0.0

    @property
    def mean_ttft_s(self) -> float:
        rr = self.request_results
        return sum(r.ttft_s for r in rr) / len(rr) if rr else 0.0

    @property
    def mean_tpot_s(self) -> float:
        rr = self.request_results
        return sum(r.tpot_s for r in rr) / len(rr) if rr else 0.0

    def ttft_percentile(self, q: float) -> float:
        rr = self.request_results
        return float(np.percentile([r.ttft_s for r in rr], q)) if rr else 0.0

    def tpot_percentile(self, q: float) -> float:
        rr = self.request_results
        return float(np.percentile([r.tpot_s for r in rr], q)) if rr else 0.0


def pad_requests(requests, pad_id: int = 0,
                 max_prompt_len: Optional[int] = None):
    """Right-pad a request chunk to its longest prompt.

    Prompts longer than ``max_prompt_len`` (when given) are truncated to it
    first.  Returns ``(tokens (B, S), lengths (B,))`` — the lengths are what
    make the padding exact downstream (prefill masks pads and gathers each
    sequence's logits at its true last token).
    """
    prompts = []
    for r in requests:
        p = np.asarray(r.prompt, np.int32).reshape(-1)
        if max_prompt_len is not None:
            p = p[:max_prompt_len]
        prompts.append(p)
    lengths = np.asarray([len(p) for p in prompts], np.int32)
    S = max(1, int(lengths.max())) if prompts else 1
    out = np.full((len(requests), S), pad_id, np.int32)
    for i, p in enumerate(prompts):
        out[i, : len(p)] = p
    return out, lengths


# ---------------------------------------------------------------------------
# Request lifecycle
# ---------------------------------------------------------------------------
class RequestHandle:
    """A submitted request's live view: status, the token stream as it is
    produced, and the timing marks the metrics derive from.

    Streaming: pass ``on_token=`` to ``Server.submit`` for a synchronous
    per-token callback, or iterate ``handle.stream()`` — the iterator
    drives ``Server.step()`` until the next token (or the end of the
    stream) is available.
    """

    def __init__(self, server: "Server", index: int, request: Request,
                 prompt: np.ndarray, decode_len: int,
                 on_token: Optional[Callable] = None) -> None:
        self._server = server
        self.index = index
        self.request = request
        self.prompt = prompt              # truncated to max_prompt_len
        self.decode_len = decode_len      # resolved fallback applied
        self.sampling = request.sampling
        self.arrival_s = float(request.arrival_s or 0.0)
        self.on_token = on_token
        # queued -> running -> finished, with running <-> preempted when
        # the server evicts the request to a host checkpoint and resumes it
        self.status = "queued"
        self.tokens: List[int] = []
        self.admit_s = float("nan")
        self.first_token_s = float("nan")
        self.finish_s = float("nan")
        self.decode_steps = 0

    @property
    def finished(self) -> bool:
        return self.status == "finished"

    def _emit(self, token: int) -> None:
        self.tokens.append(token)
        if self.on_token is not None:
            self.on_token(self, token)

    def stream(self) -> Iterator[int]:
        """Yield tokens as they are produced, driving the server forward."""
        sent = 0
        while True:
            while sent < len(self.tokens):
                yield self.tokens[sent]
                sent += 1
            if self.finished:
                return
            self._server._wait_for_arrival()
            self._server.step()

    def result(self) -> RequestResult:
        assert self.finished, f"request {self.index} is {self.status}"
        n = len(self.tokens)
        return RequestResult(
            index=self.index,
            tokens=np.asarray(self.tokens, np.int32),
            latency_s=self.finish_s - self.admit_s,
            decode_steps=self.decode_steps,
            arrival_s=self.arrival_s,
            queue_wait_s=self.admit_s - self.arrival_s,
            ttft_s=self.first_token_s - self.arrival_s,
            tpot_s=(self.finish_s - self.first_token_s) / max(1, n - 1),
        )


# ---------------------------------------------------------------------------
# The server
# ---------------------------------------------------------------------------
class Server:
    """Facade over ``ModuleBatchingEngine`` + ``ParamStore``: submit
    requests, drive them with ``step()`` / ``run()``, read the report.

    The engine (and its ``plan.B``-slot cache) is built lazily at the first
    step, sized ``min(plan.B, submitted requests)`` — submit the initial
    workload before stepping so the batch is not over-allocated.  Requests
    submitted later join the queue and reuse the existing slots; their
    prompt+decode extent must fit the realized ``max_seq``.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict,
        plan: Optional[Plan] = None,
        serve: ServeConfig = ServeConfig(),
        stream: StreamConfig = StreamConfig(),
        store: Optional[ParamStore] = None,
    ) -> None:
        if plan is None:
            plan = serve.plan
        assert plan is not None, (
            "pass a Plan, or a ServeConfig built by ServeConfig.from_plan"
        )
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.serve = serve
        self.stream = stream
        self.report = ServeReport(scheduler=serve.scheduler)
        self._store = store
        # prefix cache (needs paging; attention-only, no sliding window —
        # SSM state / ring alignment make prefixes non-transplantable)
        self._prefix = None
        if serve.prefix_cache:
            from repro.serving.cache import PrefixStore

            if PrefixStore.supported(cfg):
                self._prefix = PrefixStore(serve.kv_page_tokens)
        self._engine = None               # ModuleBatchingEngine, built lazily
        self._sampler: Optional[BatchSampler] = None
        self._handles: List[RequestHandle] = []
        self._pending: List = []          # heap of (arrival_s, index, handle)
        self._t0: Optional[float] = None
        self._max_seq: Optional[int] = serve.max_seq
        # engine-stat totals already drained into the report
        self._seen = {"drop": 0, "htod": 0, "wait": 0.0, "kvh": 0, "kvd": 0,
                      "ph": 0, "pm": 0, "lh": 0, "a2a": 0, "cd": 0,
                      "retr": 0, "tmo": 0, "per": 0, "prc": 0, "der": 0}
        # online capacity re-plan (replan_skew): the hottest expert's share
        # at the last (re-)plan; None until the first measurement
        self._replan_share: Optional[float] = None
        self._replan_ticks = 0
        # Eq. 2 admission budget (continuous): every in-flight sequence's
        # offloaded KV/state at its FULL prompt+decode extent must fit
        # m_c - S_Model, so a sequence can never outgrow the host mid-decode
        self._kv_budget = (
            None if serve.hw is None or serve.scheduler != "continuous"
            else _host_kv_budget(cfg, serve.hw)
        )
        self._kv_need: Dict[int, float] = {}
        self._live_kv = 0.0
        # slot state (allocated with the engine)
        self._b = 0
        self._free: deque = deque()
        self._slot_handle: List[Optional[RequestHandle]] = []
        self._cur: Optional[np.ndarray] = None
        self._pos: Optional[np.ndarray] = None
        self._wave: Optional[Dict] = None     # static policy's in-flight wave
        # fault tolerance (repro.faults): the resolved plan is armed around
        # every step; preempted requests wait in _ckpts (FIFO) for a slot
        self._faults = faults.resolve(serve.faults)
        self._ckpts: deque = deque()          # host-side request checkpoints
        self._ticks = 0                       # decode ticks run (virtual clock)
        self._preempt_due_at: Optional[int] = None   # next injected preempt
        self._pressure = 0                    # consecutive page-OOM events
        self._shrink_cap: Optional[int] = None   # degraded decode-chunk cap
        self._shrink_ticks = 0                # steps the shrink stays active

    # -- lifecycle: submit -------------------------------------------------
    def submit(self, request: Request,
               on_token: Optional[Callable] = None) -> RequestHandle:
        """Queue a request; it becomes admissible at ``request.arrival_s``.

        Raises ``ValueError`` immediately for a request that could never be
        served: prompt+decode beyond ``max_seq``, or (continuous with
        ``hw``) KV/state that can never fit the Eq. 2 host budget.

        Error-path invariant (validate-then-mutate): every rejection above
        raises BEFORE any server state is touched — no handle is created,
        nothing enters the arrival heap, no ``_kv_need`` entry is written
        — so a rejected submit followed by valid submits drains
        identically to never having submitted it.
        """
        serve = self.serve
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        if serve.max_prompt_len is not None:
            prompt = prompt[: serve.max_prompt_len]
        dec = max(1, int(request.decode_len or serve.decode_len))
        i = len(self._handles)
        arrival = float(request.arrival_s or 0.0)
        if not np.isfinite(arrival) or arrival < 0:
            # a NaN head would never compare due and the server would spin
            raise ValueError(
                f"request {i}: arrival_s must be finite and >= 0, "
                f"got {request.arrival_s!r}"
            )
        limit = self._max_seq
        if limit is not None and len(prompt) + dec > limit:
            raise ValueError(
                f"request {i}: prompt length {len(prompt)} + decode_len "
                f"{dec} exceeds the engine's max_seq={limit}; pass "
                f"max_prompt_len to truncate long prompts"
            )
        if self._kv_budget is not None:
            # frame-granular admission: the paged cache allocates whole
            # pages, so the charge is the page-rounded extent
            need = W.kv_bytes_per_seq(
                self.cfg, len(prompt) + dec,
                page_tokens=self.serve.kv_page_tokens,
            )
            if need > self._kv_budget:
                raise ValueError(
                    f"request {i}: KV/state bytes {need:.3e} can never fit "
                    f"the Eq. 2 host budget {self._kv_budget:.3e} (host_mem "
                    f"- model); truncate with max_prompt_len or shrink "
                    f"decode_len"
                )
        # -- all checks passed: mutate ------------------------------------
        if self._kv_budget is not None:
            self._kv_need[i] = need
        h = RequestHandle(self, i, request, prompt, dec, on_token)
        self._handles.append(h)
        heapq.heappush(self._pending, (h.arrival_s, i, h))
        return h

    # -- clock -------------------------------------------------------------
    def _now(self) -> float:
        """Virtual clock: seconds since the first step (arrivals are
        offsets on this clock)."""
        if self._t0 is None:
            self._t0 = time.perf_counter()
        return time.perf_counter() - self._t0

    @property
    def next_arrival_s(self) -> Optional[float]:
        return self._pending[0][0] if self._pending else None

    def _wait_for_arrival(self) -> None:
        """Sleep until the next queued arrival when nothing is live."""
        if self._any_live() or not self._pending:
            return
        dt = self.next_arrival_s - self._now()
        if dt > 0:
            time.sleep(min(dt, 0.05))

    # -- engine ------------------------------------------------------------
    def _ensure_engine(self) -> None:
        if self._engine is not None:
            return
        # imported here: core.engine itself imports serving.weights, so a
        # top-level import would cycle through the serving package __init__
        from repro.core.engine import ModuleBatchingEngine

        if self._store is None:
            st = self.stream
            self._store = ParamStore.build(
                self.cfg, self.params, self.plan,
                stream_weights=st.stream_weights,
                resident_bytes=st.resident_bytes, prefetch=st.prefetch,
                predict_topk=st.predict_topk, lru_bytes=st.lru_bytes,
                device=self.serve.device, sctx=self.serve.sctx,
            )
        if self.serve.max_batch is not None:
            # planner-sized up front (ServeConfig.from_plan): the engine
            # batch no longer depends on what was queued at the first step
            self._b = max(1, min(self.plan.B, int(self.serve.max_batch)))
        else:
            self._b = max(1, min(self.plan.B, len(self._handles) or 1))
        if self._max_seq is None:
            self._max_seq = max(
                len(h.prompt) + h.decode_len for h in self._handles
            )
        self._engine = ModuleBatchingEngine(
            self.cfg, self.params, self.plan, max_seq=self._max_seq,
            grouped_prefill=self.serve.grouped_prefill, store=self._store,
            cache_config=self._cache_config(),
            sctx=self.serve.sctx, ep_chunks=self.serve.ep_chunks,
        )
        self._engine.init_cache(self._b)
        self._sampler = BatchSampler(self._b)
        self._free = deque(range(self._b))
        self._slot_handle = [None] * self._b
        self._cur = np.zeros(self._b, np.int32)
        self._pos = np.zeros(self._b, np.int64)

    def _cache_config(self):
        """The ``CacheConfig`` realized from the serve knobs (None when
        paging is off — the engine keeps its contiguous buffers)."""
        if self.serve.kv_page_tokens <= 0:
            return None
        from repro.serving.cache import CacheConfig

        budget = (None if self.serve.device_kv_gb is None
                  else float(self.serve.device_kv_gb) * 1e9)
        return CacheConfig(
            page_tokens=self.serve.kv_page_tokens,
            device_pool_bytes=budget,
            prefix_cache=self._prefix is not None,
        )

    def _drain_engine_stats(self) -> int:
        """Fold the engine's cumulative counters into the report (deltas
        since the last drain); returns the expert-drop delta."""
        if self._engine is None:
            return 0
        st = self._engine.sync_stats()
        d_drop = st.expert_tokens_dropped - self._seen["drop"]
        self.report.weight_htod_bytes += st.weight_htod_bytes - self._seen["htod"]
        self.report.prefetch_wait_s += st.prefetch_wait_s - self._seen["wait"]
        self.report.kv_htod_bytes += st.kv_htod_bytes - self._seen["kvh"]
        self.report.kv_dtoh_bytes += st.kv_dtoh_bytes - self._seen["kvd"]
        self.report.expert_pred_hits += st.expert_pred_hits - self._seen["ph"]
        self.report.expert_pred_misses += (st.expert_pred_misses
                                           - self._seen["pm"])
        self.report.expert_lru_hits += st.expert_lru_hits - self._seen["lh"]
        self.report.a2a_bytes += st.a2a_bytes - self._seen["a2a"]
        self.report.collective_dispatches += (st.collective_dispatches
                                              - self._seen["cd"])
        self.report.transfer_retries += st.transfer_retries - self._seen["retr"]
        self.report.transfer_timeouts += (st.transfer_timeouts
                                          - self._seen["tmo"])
        self.report.prefill_expert_rows += (st.prefill_expert_rows
                                            - self._seen["per"])
        self.report.prefill_routed_copies += (st.prefill_routed_copies
                                              - self._seen["prc"])
        self.report.decode_expert_rows += (st.decode_expert_rows
                                           - self._seen["der"])
        # cumulative engine totals — one engine per server, so the report's
        # arrays are simply the latest snapshot (copies: the engine keeps
        # accumulating into its own buffers)
        if st.expert_tokens_dropped_by_layer is not None:
            self.report.expert_dropped_by_layer = (
                st.expert_tokens_dropped_by_layer.copy()
            )
            self.report.expert_load = st.expert_load.copy()
        self._seen = {"drop": st.expert_tokens_dropped,
                      "htod": st.weight_htod_bytes,
                      "wait": st.prefetch_wait_s,
                      "kvh": st.kv_htod_bytes,
                      "kvd": st.kv_dtoh_bytes,
                      "ph": st.expert_pred_hits,
                      "pm": st.expert_pred_misses,
                      "lh": st.expert_lru_hits,
                      "a2a": st.a2a_bytes,
                      "cd": st.collective_dispatches,
                      "retr": st.transfer_retries,
                      "tmo": st.transfer_timeouts,
                      "per": st.prefill_expert_rows,
                      "prc": st.prefill_routed_copies,
                      "der": st.decode_expert_rows}
        return d_drop

    def _maybe_replan(self) -> None:
        """Online imbalance-aware capacity re-plan: when the hottest
        expert's measured share has drifted more than ``replan_skew`` since
        the last (re-)plan, re-derive ``b_e`` from the measured per-expert
        load via ``planner.capacity_for_load`` and push it into the engine
        (``set_expert_capacity`` — the next dispatch retraces once).
        Checked every 8 decode steps to keep the host sync off the
        every-tick path."""
        self._replan_ticks += 1
        if self._replan_ticks % 8:
            return
        self.report._expert_dropped += self._drain_engine_stats()
        if self.report.expert_load is None:
            return
        per_expert = self.report.expert_load.sum(axis=0)
        total = per_expert.sum()
        if total <= 0:
            return
        share = float(per_expert.max() / total)
        if self._replan_share is None:
            self._replan_share = share       # baseline, no re-plan yet
            return
        if abs(share - self._replan_share) <= self.serve.replan_skew:
            return
        from repro.core.planner import capacity_for_load

        b_e = capacity_for_load(
            per_expert, self._b, self.cfg.experts_per_token,
            max_drop_rate=self.serve.replan_drop_target,
        )
        self._engine.set_expert_capacity(b_e)
        self._replan_share = share
        self.report.capacity_replans += 1

    # -- the step-driven core ---------------------------------------------
    def _any_live(self) -> bool:
        return any(h is not None for h in self._slot_handle)

    def has_work(self) -> bool:
        return (self._any_live() or bool(self._pending)
                or bool(self._ckpts))

    def step(self) -> bool:
        """One scheduler tick: admit due arrivals (policy-dependent), run
        one module-batched decode step over every slot, sample each live
        slot under its own ``SamplingParams``, finish/evict/recycle.
        Returns True while work remains (live slots, queued requests, or
        preempted checkpoints); with only future arrivals pending it
        returns True without decoding — ``run()`` sleeps through such
        gaps, manual steppers can watch ``next_arrival_s``.

        The whole tick runs with the server's fault plan armed
        (``ServeConfig.faults``; a pass-through to the ambient
        ``REPRO_FAULTS`` plan when unset), so every stream / page /
        preemption seam underneath consults the same schedule.
        """
        if not self.has_work():
            return False
        with span("step", step=self._ticks), self._on_device():
            self._ensure_engine()
            with faults.armed(self._faults):
                self._maybe_preempt()
                self._admit()
                if self._any_live():
                    self._decode_tick(self._chunk_T())
                    if self.serve.replan_skew is not None:
                        self._maybe_replan()
        return self.has_work()

    def _on_device(self):
        """Make the server's device JAX's default for the work inside, so
        the arrays the engine creates (KV, sampler state, counters) land
        beside its weights."""
        if self.serve.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.serve.device)

    def run(self, until_idle: bool = True) -> ServeReport:
        """Drive ``step()`` to completion and return the report.

        ``until_idle=False`` stops at the first moment nothing is live or
        due (future arrivals are left queued) instead of sleeping for them.
        """
        while self.step():
            if not self._any_live() and self._pending:
                if not until_idle and self.next_arrival_s > self._now():
                    break
                self._wait_for_arrival()
        return self.finalize()

    def finalize(self) -> ServeReport:
        """Drain engine counters and order results; idempotent."""
        with self._on_device():
            self.report._expert_dropped += self._drain_engine_stats()
        if self._prefix is not None:
            self.report.prefix_hits = self._prefix.hits
            self.report.prefix_misses = self._prefix.misses
        self.report.request_results.sort(key=lambda r: r.index)
        return self.report

    # -- admission policies ------------------------------------------------
    def _pop_due(self, now: float) -> Optional[RequestHandle]:
        """Pop the queue head if it has arrived (FIFO in arrival order —
        later requests are never reordered past a waiting head)."""
        if self._pending and self._pending[0][0] <= now:
            return heapq.heappop(self._pending)[2]
        return None

    def _admit(self) -> None:
        with span("admit"):
            if self.serve.scheduler == "static":
                self._admit_static()
            else:
                self._admit_continuous()

    def _admit_static(self) -> None:
        """Admit-in-waves policy: a new wave only once the previous wave has
        fully drained; the wave takes every due request up to B slots."""
        if self._wave is not None:
            return
        now = self._now()
        handles: List[RequestHandle] = []
        while len(handles) < self._b:
            h = self._pop_due(now)
            if h is None:
                break
            # reserve the wave slot's page frames up front: an OOM (real
            # exhaustion or injected) degrades — requeue + demote/shrink —
            # instead of aborting mid-prefill
            try:
                self._engine.reserve_slot_rows([len(handles)])
            except faults.PageAllocOOM as err:
                heapq.heappush(self._pending, (h.arrival_s, h.index, h))
                self._degrade_on_oom(err)
                break
            self._pressure = 0
            handles.append(h)
        if not handles:
            return
        slots = list(range(len(handles)))
        self._wave = {
            "slots": slots, "handles": handles,
            "rows": [[] for _ in slots], "done": [False] * len(slots),
            "ticks": 0, "prefill_s": 0.0, "decode_s": 0.0,
        }
        self._prefill_wave(handles, slots)
        if all(self._wave["done"]):
            self._close_wave()

    def _admit_continuous(self) -> None:
        """Admit/evict policy: prefill due requests into freed slots (one
        batched prefill per admission wave; insta-finishers free their slot
        again, so loop until stable).  With an Eq. 2 budget the queue head
        WAITS while its KV bytes don't fit next to the in-flight
        sequences' (FIFO — later smaller requests are not reordered past
        it).  Preempted checkpoints resume FIRST (they were admitted
        before anything still queued), restoring their KV rows and sampler
        token index with zero prefill relaunches."""
        now = self._now()
        self._resume_checkpoints(now)
        blocked = False
        while (not blocked and self._free and self._pending
               and self._pending[0][0] <= now):
            slots, handles = [], []
            while self._free and self._pending and self._pending[0][0] <= now:
                i = self._pending[0][1]
                if (self._kv_budget is not None
                        and self._live_kv + self._kv_need[i] > self._kv_budget):
                    break              # head waits for an eviction
                h = heapq.heappop(self._pending)[2]
                s = self._free.popleft()
                # page-frame reservation up front: an OOM (real exhaustion
                # or injected) degrades — defer/demote/shrink — instead of
                # aborting mid-prefill; the handle goes back to the head
                try:
                    self._engine.reserve_slot_rows([s])
                except faults.PageAllocOOM as err:
                    self._free.appendleft(s)
                    heapq.heappush(self._pending, (h.arrival_s, h.index, h))
                    self._degrade_on_oom(err)
                    blocked = True
                    break
                self._pressure = 0
                slots.append(s)
                handles.append(h)
                if self._kv_budget is not None:
                    self._live_kv += self._kv_need[i]
            if not handles:
                break                  # nothing admissible this attempt
            self._prefill_wave(handles, slots)
        # counted ONCE per admission attempt: the head is due but leaving
        # this attempt memory-blocked despite a free slot
        if (self._kv_budget is not None and self._free and self._pending
                and self._pending[0][0] <= now
                and self._live_kv + self._kv_need[self._pending[0][1]]
                > self._kv_budget):
            self.report.admission_deferrals += 1

    # -- fault tolerance: preempt / checkpoint / resume --------------------
    def preempt(self, handle: RequestHandle) -> bool:
        """Evict a RUNNING request to a host-side checkpoint (KV/state
        rows + current token + position; the sampler key/step restore from
        the handle itself).  The slot, page frames and sampler slot are
        freed for other requests; the checkpoint re-admits prefix-style
        (``_resume_checkpoints``) with ZERO prefill relaunches, and —
        because sampling is keyed on ``(seed, token_index)`` — the resumed
        stream is bit-identical to an unpreempted run.

        Continuous scheduler only (a static wave drains in place — its
        slots cannot be recycled mid-wave).  Returns False when the handle
        is not currently running."""
        assert self.serve.scheduler == "continuous", (
            "preemption is a continuous-scheduler policy"
        )
        if handle.status != "running":
            return False
        with self._on_device():
            self._preempt_slot(self._slot_handle.index(handle), self._now())
        return True

    def _preempt_slot(self, s: int, now: float) -> None:
        h = self._slot_handle[s]
        ckpt = {
            "handle": h,
            "state": self._engine.checkpoint_slot(s),
            "cur": int(self._cur[s]),
            "pos": int(self._pos[s]),
        }
        h.status = "preempted"
        if self._kv_budget is not None:
            self._live_kv -= self._kv_need[h.index]
        self._slot_handle[s] = None
        self._sampler.clear_slot(s)
        self._engine.evict_slots([s])
        self._free.append(s)
        self._ckpts.append(ckpt)
        self.report.preemptions += 1
        faults.note("preempt")

    def _resume_checkpoints(self, now: float) -> None:
        """Re-admit preempted checkpoints (FIFO) into free slots: restore
        the KV/state rows eagerly, re-arm the sampler slot at the exact
        token index already emitted (``set_slot`` + ``advance`` — the
        determinism contract), and restore the current token/position.  No
        prefill launch is issued."""
        while self._ckpts and self._free:
            h = self._ckpts[0]["handle"]
            if (self._kv_budget is not None
                    and self._live_kv + self._kv_need[h.index]
                    > self._kv_budget):
                break
            s = self._free[0]
            try:
                self._engine.restore_slot(s, self._ckpts[0]["state"])
            except faults.PageAllocOOM as err:
                self._degrade_on_oom(err)
                break
            self._pressure = 0
            ckpt = self._ckpts.popleft()
            self._free.popleft()
            self._sampler.set_slot(s, h.sampling)
            self._sampler.advance([s], len(h.tokens))
            self._slot_handle[s] = h
            self._cur[s] = ckpt["cur"]
            self._pos[s] = ckpt["pos"]
            if self._kv_budget is not None:
                self._live_kv += self._kv_need[h.index]
            h.status = "running"
            self.report.resumes += 1
            faults.note("resume")

    def _maybe_preempt(self) -> None:
        """Injected preemption (chaos schedules): every
        ``spec.preempt_every`` decode ticks, preempt the lowest-slot
        running request (continuous only — static waves drain in place).
        Progress is guaranteed: the checkpoint resumes at the next
        admission and the tick clock only advances while decoding, so a
        preempt/resume cycle always decodes between preemptions."""
        if self.serve.scheduler != "continuous":
            return
        fp = faults.current()
        if fp is None or fp.spec.preempt_every <= 0:
            return
        if self._preempt_due_at is None:
            self._preempt_due_at = fp.spec.preempt_every
        if self._ticks < self._preempt_due_at:
            return
        victims = [s for s in range(self._b)
                   if self._slot_handle[s] is not None
                   and not self._slot_handle[s].finished]
        if not victims:
            return
        self._preempt_due_at = self._ticks + fp.spec.preempt_every
        fp.note("injected:preempt")
        self._preempt_slot(min(victims), self._now())

    def _degrade_on_oom(self, err: Exception) -> None:
        """Memory-pressure degradation ladder (counted, escalating with
        consecutive pressure): (1) defer the admission — the handle is
        already requeued at the head; (2) demote live device page frames
        to the host tier; (3) shrink the fused decode-chunk cap so frames
        recycle at finer granularity.  Fails loudly (re-raise) only when
        the request is unservable: no fault plan armed and nothing live
        whose eviction could ever free frames."""
        if faults.current() is None and not self._any_live():
            raise err
        self._pressure += 1
        self.report.degrade_deferrals += 1
        faults.note("recovered:admission-deferral")
        pages = self._engine.pages
        if self._pressure >= 2 and pages is not None:
            moved = pages.demote_device_frames(pages.pages_per_seq)
            self.report.page_demotions += moved
        if self._pressure >= 3:
            cap = int(self.serve.decode_chunk
                      or getattr(self.plan, "decode_chunk", 1) or 1)
            base = self._shrink_cap if self._shrink_cap is not None else cap
            self._shrink_cap = max(1, base // 2)
            self._shrink_ticks = 16
            self.report.chunk_shrinks += 1
            faults.note("recovered:chunk-shrink")

    # -- shared prefill / decode / finish ----------------------------------
    def _prefill_wave(self, handles: List[RequestHandle],
                      slots: List[int]) -> None:
        """One batched prefill of ``handles`` into ``slots``: writes their
        KV/state rows, arms their sampler slots, and emits each request's
        FIRST token (sampled from the prefill logits).

        With the prefix cache on, the wave is partitioned: HITS are
        admitted per handle through ``engine.prefill_prefix_hit`` (the
        stored prefix pages are copied in; only the suffix is computed —
        zero prefill launches for the shared span), MISSES take the
        batched prefill and donate their prefix rows to the store
        afterwards.  Tokens are identical either way (per-slot seeded
        sampling; copied KV equals recomputed KV)."""
        with span("prefill", rows=len(handles),
                  tokens=sum(len(h.prompt) for h in handles)):
            engine, sampler = self._engine, self._sampler
            t0 = self._now()
            hits: List = []
            misses, miss_slots = list(handles), list(slots)
            if self._prefix is not None:
                hits, misses, miss_slots = [], [], []
                for h, s in zip(handles, slots):
                    kp = self._prefix.key(h.prompt)
                    kvs = None if kp is None else self._prefix.get(kp[0])
                    if kvs is not None:
                        hits.append((h, s, kp[1], kvs))
                    else:
                        misses.append(h)
                        miss_slots.append(s)
            for h, s in zip(handles, slots):
                sampler.set_slot(s, h.sampling)
            tok0: Dict[int, int] = {}
            if misses:
                self.report.prefill_tokens += sum(len(h.prompt)
                                                  for h in misses)
                ptoks, lens = pad_requests(misses, self.serve.pad_id)
                lg = engine.prefill_slots(jnp.asarray(ptoks), miss_slots,
                                          lengths=lens)
                for s, tk in zip(miss_slots,
                                 np.asarray(sampler.sample(lg, miss_slots))):
                    tok0[s] = int(tk)
                if self._prefix is not None:
                    for h, s in zip(misses, miss_slots):
                        kp = self._prefix.key(h.prompt)
                        if kp is not None:
                            self._prefix.put(
                                kp[0], engine.read_prefix_rows(s, kp[1])
                            )
            for h, s, pspan, kvs in hits:
                self.report.prefill_tokens += len(h.prompt) - pspan
                lg = engine.prefill_prefix_hit(s, h.prompt, kvs, pspan)
                tok0[s] = int(np.asarray(sampler.sample(lg, [s]))[0])
            now = self._now()
            self.report.prefill_s += now - t0
            if self._wave is not None:
                self._wave["prefill_s"] += now - t0
            eos = self.serve.eos_id
            for h, s in zip(handles, slots):
                tk = tok0[s]
                self._slot_handle[s] = h
                self._pos[s] = len(h.prompt)
                self._cur[s] = tk
                h.status = "running"
                h.admit_s = t0
                h.first_token_s = now
                h._emit(tk)
                if self._wave is not None:
                    self._wave["rows"][s] = [tk]
                if h.decode_len <= 1 or (eos is not None and tk == eos):
                    self._finish_slot(s, now)

    def _chunk_T(self) -> int:
        """Decode ticks to run this step as ONE fused multi-token chunk.

        Chunking is the module-batching thesis applied to the scheduler:
        when no admission or eviction can fall due mid-chunk, ``T`` decode
        ticks cost one device dispatch (``engine.decode_chunk``) instead of
        ``T``.  ``T`` is capped by the plan's ``decode_chunk`` (or the
        ``ServeConfig`` override) and clamped to the SHORTEST remaining
        decode among unfinished slots, so every finish event still lands
        exactly at a chunk boundary (timestamps, eviction and §5.1 waste
        accounting are tick-identical to per-tick stepping).  Falls back to
        1 when: an ``eos_id`` is set (finishes are unpredictable), the
        engine is not fused-eligible (streamed weights keep the per-layer
        loop), or — continuous mode — a queued arrival could be admitted
        into a free slot mid-chunk.
        """
        cap = self.serve.decode_chunk or getattr(self.plan, "decode_chunk", 1)
        if self._shrink_ticks > 0:
            # memory-pressure degradation stage 3: finer chunks recycle
            # page frames at finer granularity (decays back to the
            # configured cap after _shrink_ticks steps)
            cap = min(int(cap), self._shrink_cap)
            self._shrink_ticks -= 1
            if self._shrink_ticks == 0:
                self._shrink_cap = None
        fp = faults.current()
        if (fp is not None and fp.spec.preempt_every > 0
                and self.serve.scheduler == "continuous"):
            # an injected preemption can only land at a chunk boundary —
            # clamp T so the tick clock stops exactly at the next scheduled
            # preempt (chunking-only: the decoded tokens are unchanged)
            due = (self._preempt_due_at if self._preempt_due_at is not None
                   else fp.spec.preempt_every)
            if due > self._ticks:
                cap = min(int(cap), due - self._ticks)
        if cap <= 1 or self.serve.eos_id is not None:
            return 1
        if not self._engine.fused_eligible():
            return 1
        if self._wave is not None:
            rem = [h.decode_len - len(h.tokens)
                   for h, d in zip(self._wave["handles"], self._wave["done"])
                   if not d]
        else:
            if (self._pending or self._ckpts) and self._free:
                return 1               # a due arrival/resume could admit
            rem = [h.decode_len - len(h.tokens)
                   for h in self._slot_handle
                   if h is not None and not h.finished]
        if not rem:
            return 1
        return max(1, min(int(cap), min(rem)))

    @hot_path
    def _decode_tick(self, T: int = 1) -> None:
        """``T`` module-batched decode ticks over the full engine batch —
        ONE fused device dispatch when the engine's fused path is eligible;
        live slots emit their sampled tokens tick by tick, finishers are
        handed to the policy's finish path."""
        engine, sampler = self._engine, self._sampler
        wave = self._wave
        # rows the scheduler advances each tick: wave slots (finished
        # members keep stepping until the drain) or handle-owning slots.
        # Dead rows hold their stale token/position inside the chunk,
        # exactly like per-tick stepping never updates a free slot.
        live = np.zeros(self._b, bool)
        if wave is not None:
            live[wave["slots"]] = True
        else:
            live[[s for s in range(self._b)
                  if self._slot_handle[s] is not None]] = True
        t0 = self._now()
        with span("decode", T=T):
            toks = engine.decode_chunk(
                jnp.asarray(self._cur), jnp.asarray(self._pos), sampler, T,
                live=live,
            )
        with sanitizer.allowed("token-readback"):
            mat = np.asarray(toks)  # lint: allow[MG101] the per-chunk token readback — the ONE planned d2h sync per scheduler tick
        now = self._now()
        self._ticks += T
        self.report.decode_s += now - t0
        if wave is not None:
            wave["decode_s"] += now - t0
        with span("emit", T=T):
            self._emit_chunk(mat, T, now)

    @hot_path
    def _emit_chunk(self, mat: np.ndarray, T: int, now: float) -> None:
        """Emit the ``(B, T)`` chunk of read-back tokens tick by tick:
        live slots take their tokens, finishers go to the policy's finish
        path, and a static wave closes at its drain."""
        wave = self._wave
        counted = len(wave["slots"]) if wave is not None else self._b
        eos = self.serve.eos_id
        n_mla = sum(1 for k, _ in self._engine.schema if k == "mla")
        for t in range(T):
            nxt = mat[:, t]
            live = [s for s in range(self._b)
                    if self._slot_handle[s] is not None
                    and not self._slot_handle[s].finished]
            self.report.decode_slot_steps += counted
            self.report.wasted_slot_steps += counted - len(live)
            if n_mla and live:
                # row s was fed at _pos[s] (the engine clamps at max_seq-1)
                fed = np.minimum(self._pos[live], self._max_seq - 1)
                self.report.decode_ctx_tokens += n_mla * int((fed + 1).sum())
            for s in live:
                h = self._slot_handle[s]
                tk = int(nxt[s])
                h._emit(tk)
                if len(h.tokens) >= h.decode_len or (
                        eos is not None and tk == eos):
                    self._finish_slot(s, now)
            if wave is not None:
                # the wave keeps stepping finished slots until its slowest
                # member drains — record their raw chain (paper §5.1 static
                # batches; the waste is the mode's defining metric)
                wave["ticks"] += 1
                for s in wave["slots"]:
                    wave["rows"][s].append(int(nxt[s]))
                    self._cur[s] = nxt[s]
                    self._pos[s] += 1
                if all(wave["done"]):
                    self._close_wave()
                    break              # _chunk_T ends chunks at the drain
            else:
                for s in range(self._b):
                    if self._slot_handle[s] is not None:
                        self._cur[s] = nxt[s]
                        self._pos[s] += 1

    def _finish_slot(self, s: int, now: float) -> None:
        h = self._slot_handle[s]
        h.status = "finished"
        h.finish_s = now
        if self._wave is not None:                      # static: keep the
            self._wave["done"][self._wave["slots"].index(s)] = True
            return                                      # slot until drain
        h.decode_steps = len(h.tokens) - 1
        self.report.request_results.append(h.result())
        if self._kv_budget is not None:
            self._live_kv -= self._kv_need[h.index]
        self._slot_handle[s] = None
        self._sampler.clear_slot(s)
        self._engine.evict_slots([s])
        self._free.append(s)

    def _close_wave(self) -> None:
        """Static wave drained: record its BatchResult (raw token matrix,
        old-protocol shape) and per-request results, then free the slots."""
        wave, self._wave = self._wave, None
        ticks = wave["ticks"]
        for h, s in zip(wave["handles"], wave["slots"]):
            h.decode_steps = ticks
            self.report.request_results.append(h.result())
            self._slot_handle[s] = None
            self._sampler.clear_slot(s)
        self._engine.evict_slots(wave["slots"])
        self._free = deque(range(self._b))
        mat = np.asarray([wave["rows"][s] for s in wave["slots"]], np.int64)
        self.report.results.append(BatchResult(
            mat, wave["prefill_s"], wave["decode_s"],
            self._drain_engine_stats(),
        ))


def _host_kv_budget(cfg: ModelConfig, hw: HardwareProfile) -> float:
    from repro.core.planner import host_kv_budget

    return host_kv_budget(cfg, hw)
