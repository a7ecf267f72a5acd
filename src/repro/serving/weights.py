"""Streamed parameter store: weight residency + double-buffered prefetch.

The paper's headline mechanism (Fig. 6; ``S_Expert``/``S_Params`` in
Table 2) is that expert weights live in HOST memory and are streamed
device-ward on an htod channel that hides behind the grouped expert GEMM.
``ParamStore`` is the executor side of that policy:

* the **resident set** is pinned on device, greedily filled up to
  ``Plan.s_params`` by ``core.workload.plan_residency`` — the SAME policy
  the planner's cost model charges misses with, so the planner's predicted
  overlap is measurable against the real engine.  Base weights
  (embedding / final norm / lm_head) are always pinned; sequence mixers and
  norms fill next, expert stacks last.
* the **streamed set** is kept host-side (numpy — the pinned-host analogue
  on this backend) and served through a bounded in-flight window of
  ``prefetch_depth`` per-layer modules (the double buffer ``Plan.s_expert``
  sizes): the engine issues ``prefetch(l+1)`` as soon as ``acquire(l)``
  returns, before layer *l*'s mixer stage, so ``jax.device_put``'s async
  copy is in flight while the host dispatches layer *l*; ``acquire(l)``
  consumes the in-flight transfer (or fetches on demand when prefetch is
  off — the streamed-serial baseline of the ``weight_streaming``
  benchmark).  At that issue point the device holds two layers' streamed
  modules: *l*, just landed, and *l+1*, in flight.

The store keeps device-side accounting (htod bytes at issue time, stall
seconds at acquire time) that ``ModuleBatchingEngine.sync_stats`` folds
into ``EngineStats`` and the scheduler surfaces as ``ServeReport.htod_gb``
/ ``prefetch_wait_s``.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import faults
from repro.analysis import runtime as sanitizer
from repro.analysis.spans import span
from repro.configs.base import ModelConfig
from repro.core import workload as W
from repro.models import model as model_mod

# per-layer module split: the streaming granularity.  'mixer' is
# norm1 + attention/latent attention/SSM; 'ffn' is norm2 + (MoE stacks +
# router | dense FFN).
_MIXER_KEYS = ("norm1", "attn", "mla", "ssm")
_FFN_KEYS = ("norm2", "moe", "ffn")


def unstack_layers(cfg: ModelConfig, params: Dict) -> List[Tuple[str, str, Dict]]:
    """Flatten group-stacked layer params into a per-layer list (the
    layers after the leading dense ones, which ``params["lead"]`` holds
    unstacked)."""
    pattern = model_mod.layer_pattern(cfg)
    G = model_mod.num_groups(cfg)
    layers = []
    for g in range(G):
        for j, (kind, ffn) in enumerate(pattern):
            slot = jax.tree.map(lambda a: a[g], params["layers"][j])
            layers.append((kind, ffn, slot))
    return layers


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))


def _to_host(tree):
    return jax.tree.map(np.asarray, tree)


class _StalledTransfer:
    """An injected dead in-flight transfer: parked in the window like a
    real value but never becomes ready, so ``acquire`` exercises the
    watchdog recovery path without real wall-clock waiting."""

    def __init__(self, value) -> None:
        self.value = value


class StreamWindow:
    """Bounded in-flight window of async htod transfers (the double buffer).

    The generic half of the PR 3 streaming design, shared by weight
    streaming (``ParamStore``) and KV-page streaming
    (``serving.cache.KVPageTable``): ``prefetch(key)`` issues the async
    ``jax.device_put`` copy produced by the ``fetch`` closure and parks it
    in a window of at most ``depth`` in-flight entries (oldest evicted);
    ``acquire(key)`` consumes the in-flight transfer — or fetches on
    demand when it was never staged — and accounts the stall seconds spent
    blocking on it.  ``fetch(key) -> (value, nbytes)`` must return the
    device-side value plus the bytes it moved; copies are issued at
    prefetch/fetch time, so ``htod_bytes`` counts issue-side traffic.

    ``tag`` names the planned-transfer scope every copy through this window
    is issued under, so the runtime sanitizer can attribute traffic per
    stream (``stream-window`` for whole-module staging, ``expert-prefetch``
    for the predictive per-expert window, ``kv-pages`` for host KV
    frames).  Each copy is the program span ``xfer`` with the tag and its
    key, and each blocking wait in ``acquire`` the span ``stream.wait``
    with the tag, key and bytes of the copy it waits for.

    Fault tolerance: every fetch consults the armed ``faults`` plan (a
    no-op when unarmed) and retries transient failures under the shared
    ``RetryPolicy`` with capped exponential backoff (retried copies run
    in the ``fault-retry`` planned-transfer scope).  With a finite
    ``retry.watchdog_s`` the blocking ``acquire`` wait polls device-buffer
    readiness against a deadline: a dead/stalled in-flight entry is
    abandoned and demand re-fetched once, and only then surfaces as a
    ``StreamTimeoutError`` naming the window tag and key — the historical
    behavior (``watchdog_s=None``) blocked forever.
    """

    def __init__(
        self, fetch, depth: int = 2, enabled: bool = True,
        tag: str = "stream-window", retry: Optional[faults.RetryPolicy] = None,
    ) -> None:
        self._fetch = fetch
        self.tag = tag
        self.depth = max(1, depth)
        self.enabled = enabled
        self.retry = retry if retry is not None else faults.RetryPolicy()
        self.inflight: Dict = {}             # key -> (value, nbytes)
        self._order: List = []
        self.htod_bytes = 0
        self.wait_s = 0.0
        self.issued = 0
        self.demand = 0
        self.retries = 0
        self.timeouts = 0

    def _issue(self, key):
        """One fetch attempt, with injected transient failures."""
        fp = faults.current()
        if fp is not None and fp.transfer_fault(self.tag, key):
            raise faults.TransientTransferError(
                f"injected transient transfer fault "
                f"(window {self.tag!r}, key {key!r})")
        return self._fetch(key)

    def _issue_with_retry(self, key, recovery: bool = False):
        """Fetch under the shared retry policy: the first attempt runs in
        this window's planned-transfer scope, retries in ``fault-retry``
        (every attempt of a ``recovery`` re-fetch is retry traffic)."""
        delay = self.retry.backoff_s
        for attempt in range(self.retry.max_retries + 1):
            try:
                scope = ("fault-retry" if recovery or attempt > 0
                         else self.tag)
                with sanitizer.allowed(scope, key=key):
                    return self._issue(key)
            except faults.TransientTransferError:
                if attempt >= self.retry.max_retries:
                    raise
                self.retries += 1
                faults.note(f"recovered:transfer-retry:{self.tag}")
                if delay > 0.0:
                    time.sleep(min(delay, self.retry.backoff_cap_s))
                delay = min(delay * 2.0, self.retry.backoff_cap_s or delay)
        raise AssertionError("unreachable")

    def _wait_ready(self, value) -> bool:
        """Block until ``value``'s buffers land; ``False`` on watchdog
        expiry (never with ``watchdog_s=None`` — unbounded wait)."""
        if isinstance(value, _StalledTransfer):
            return False
        if self.retry.watchdog_s is None:
            jax.block_until_ready(value)
            return True
        deadline = time.perf_counter() + self.retry.watchdog_s
        for leaf in jax.tree.leaves(value):
            poll = getattr(leaf, "is_ready", None)
            if poll is None:
                continue
            while not poll():
                if time.perf_counter() >= deadline:
                    return False
                time.sleep(0.0005)
        jax.block_until_ready(
            [x for x in jax.tree.leaves(value) if isinstance(x, jax.Array)])
        return True

    def _wait(self, value, key, nbytes: int, demand: bool) -> bool:
        """``_wait_ready`` in the span ``stream.wait``, its seconds
        accounted in ``wait_s``."""
        t0 = time.perf_counter()
        with span("stream.wait", tag=self.tag, key=key, bytes=nbytes,
                  demand=int(demand)):
            ok = self._wait_ready(value)
        self.wait_s += time.perf_counter() - t0
        return ok

    def prefetch(self, key) -> None:
        """Stage ``key``'s transfer into the window (async; returns
        immediately).  No-op when disabled or already in flight."""
        if not self.enabled or key in self.inflight:
            return
        while len(self._order) >= self.depth:
            oldest = self._order.pop(0)
            self.inflight.pop(oldest, None)
        value, nbytes = self._issue_with_retry(key)
        fp = faults.current()
        if fp is not None and fp.stall_fault(self.tag, key):
            value = _StalledTransfer(value)
        self.inflight[key] = (value, nbytes)
        self._order.append(key)
        self.htod_bytes += nbytes
        self.issued += 1

    def acquire(self, key):
        """Consume ``key``'s in-flight transfer (or fetch on demand),
        blocking until the copy lands; the stall is accounted in
        ``wait_s``.  A wait that exceeds ``retry.watchdog_s`` (or an
        injected stalled transfer) is recovered by abandoning the dead
        entry and demand re-fetching once; a second expiry raises
        ``StreamTimeoutError`` with the window tag and key."""
        demand = key not in self.inflight
        if demand:
            value, nbytes = self._issue_with_retry(key)
            self.htod_bytes += nbytes
            self.demand += 1
        else:
            value, nbytes = self.inflight.pop(key)
            self._order.remove(key)
        ok = self._wait(value, key, nbytes, demand)
        if ok:
            return value
        self.timeouts += 1
        faults.note(f"recovered:transfer-timeout:{self.tag}")
        try:
            value, nbytes = self._issue_with_retry(key, recovery=True)
        except faults.TransientTransferError as e:
            raise faults.StreamTimeoutError(
                f"stalled stream transfer and the recovery fetch failed "
                f"after {self.retry.max_retries} retries "
                f"(window {self.tag!r}, key {key!r})") from e
        self.htod_bytes += nbytes
        self.demand += 1
        ok = self._wait(value, key, nbytes, True)
        if not ok:
            raise faults.StreamTimeoutError(
                f"stream transfer stalled twice (watchdog "
                f"{self.retry.watchdog_s}s; window {self.tag!r}, "
                f"key {key!r})")
        return value

    def take_counters(self) -> Tuple[int, float]:
        """Drain (htod_bytes, wait_s) since the last call."""
        out = (self.htod_bytes, self.wait_s)
        self.htod_bytes = 0
        self.wait_s = 0.0
        return out

    def take_fault_counters(self) -> Tuple[int, int]:
        """Drain (retries, timeouts) since the last call."""
        out = (self.retries, self.timeouts)
        self.retries = 0
        self.timeouts = 0
        return out


class ParamStore:
    """Weight-residency subsystem the engine executes through.

    ``resident_bytes=None`` pins everything on device (the default engine
    behavior — streaming is opt-in).  Any finite budget realizes the greedy
    ``workload.plan_residency`` split; ``resident_bytes=0`` streams every
    per-layer module (base weights stay pinned).

    ``prefetch=True`` is the overlapped mode: ``prefetch(l)`` issues the
    async htod copy of layer *l*'s streamed modules into the in-flight
    window ahead of use.  ``prefetch=False`` fetches on demand at
    ``acquire`` — the serialized copy->compute baseline.

    ``predict_topk > 0`` switches streamed MoE layers to PREDICTIVE
    PER-EXPERT streaming: the expert stacks are split into per-expert host
    handles served through a second ``StreamWindow`` (planned-transfer tag
    ``expert-prefetch``), while the layer's norm2 + router — tiny, and the
    router is needed on device to predict the NEXT layer's expert set —
    stay pinned.  ``prefetch_experts(l+1, predicted)`` stages only the
    predicted set; ``acquire_experts(l, used)`` assembles the grouped-GEMM
    stacks from the in-flight set, the hot-expert LRU, and on-demand
    fetches for mispredictions — prediction moves WHEN bytes move, never
    WHICH math runs.  ``lru_bytes`` (default: the residency plan's spare
    bytes) bounds a device-side hot-expert LRU: every expert use promotes
    its weights; cold experts are demoted when the byte budget overflows.

    ``params`` may hold device arrays or host numpy arrays (as
    ``models.model.init_params_host`` makes them): resident modules are
    copied to ``device`` (JAX's default device when None) and streamed ones
    stay on the host, so a model larger than device memory is never whole
    on the device.  With ``sctx`` (an expert-parallel mesh) every resident
    expert stack is placed sharded over ``sctx.model_axis`` instead — each
    device holds only its own experts, the shards built straight from the
    host copy.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict,
        resident_bytes: Optional[float] = None,
        prefetch: bool = True,
        prefetch_depth: int = 2,
        predict_topk: int = 0,
        lru_bytes: Optional[float] = None,
        device=None,
        sctx=None,
    ) -> None:
        self.cfg = cfg
        self.device = device
        self._expert_sharding = (
            NamedSharding(sctx.mesh, P(sctx.model_axis, None, None))
            if sctx is not None and sctx.mesh is not None
            and sctx.model_axis is not None else None
        )
        self.prefetch_enabled = prefetch
        self.prefetch_depth = max(1, prefetch_depth)
        self.residency = W.plan_residency(cfg, resident_bytes)
        self.predict_topk = (
            max(0, min(cfg.num_experts, int(predict_topk)))
            if cfg.has_moe else 0
        )
        layers = unstack_layers(cfg, params)
        self.schema: List[Tuple[str, str]] = [(k, f) for k, f, _ in layers]
        # base params: always device-resident (embed / final_norm / lm_head)
        self.base: Dict = self._pin(
            {k: v for k, v in params.items() if k not in ("layers", "lead")}
        )
        # leading dense layers (``first_k_dense``): pinned like the base
        # weights and outside the layer index, which counts the periodic
        # stack only — ``acquire(0)`` is the first layer after them
        self.lead_schema: List[Tuple[str, str]] = model_mod.lead_pattern(cfg)
        self.lead: List[Dict] = [self._pin(p)
                                 for p in params.get("lead", [])]
        # per-layer split into resident (device) and streamed (host) modules
        self._resident: List[Dict[str, Dict]] = []
        self._host: List[Dict[str, Dict]] = []
        # predictive split of streamed MoE layers: norm2 + router pinned
        # device-side (keyed by layer), expert stacks host-side as
        # per-expert slices (numpy views — zero-copy)
        self._moe_shared: Dict[int, Dict] = {}
        self._experts_host: Dict[int, Dict[str, np.ndarray]] = {}
        for li, (kind, ffn, slot) in enumerate(layers):
            mixer = {k: v for k, v in slot.items() if k in _MIXER_KEYS}
            ffnp = {k: v for k, v in slot.items() if k in _FFN_KEYS}
            res: Dict[str, Dict] = {}
            host: Dict[str, Dict] = {}
            mi = li + cfg.first_k_dense     # the residency plan's index
            if self.residency.mixer_resident[mi]:
                res["mixer"] = self._pin(mixer)
            else:
                host["mixer"] = _to_host(mixer)
            if ffnp:
                if self.residency.ffn_resident[mi]:
                    res["ffn"] = self._pin(ffnp)
                elif self.predict_topk > 0 and ffn == "moe":
                    self._moe_shared[li] = self._pin({
                        "norm2": ffnp["norm2"],
                        "router": ffnp["moe"]["router"],
                        **({"shared": ffnp["moe"]["shared"]}
                           if "shared" in ffnp["moe"] else {}),
                    })
                    self._experts_host[li] = {
                        k: np.asarray(ffnp["moe"][k])
                        for k in ("experts_w_gate", "experts_w_up",
                                  "experts_w_down")
                    }
                else:
                    host["ffn"] = _to_host(ffnp)
            self._resident.append(res)
            self._host.append(host)
        # the double-buffer window: in-flight prefetched layer transfers,
        # bounded at prefetch_depth (shared machinery with KV-page
        # streaming — see StreamWindow)
        self._window = StreamWindow(
            self._fetch, depth=self.prefetch_depth, enabled=True
        )
        # predictive per-expert window: keys are (layer, expert).  Depth
        # covers two layers' worth of whole stacks so prefill's all-expert
        # staging and back-to-back predicted sets never thrash each other.
        self._expert_window = StreamWindow(
            self._fetch_expert,
            depth=2 * max(1, cfg.num_experts),
            enabled=True,
            tag="expert-prefetch",
        )
        # hot-expert LRU: (layer, expert) -> (device tree, nbytes).  Usage
        # promotes (move-to-end); overflow demotes the coldest entry.  The
        # byte budget defaults to whatever the greedy residency fill left
        # unused — bytes the planner already reserved for weights.
        self._lru: "OrderedDict[Tuple[int, int], Tuple[Tuple, int]]" = (
            OrderedDict()
        )
        self.lru_bytes = float(
            self.residency.spare_bytes if lru_bytes is None else lru_bytes
        )
        self._lru_used = 0
        self._expert_counters = {
            "pred_hits": 0, "pred_misses": 0, "lru_hits": 0,
        }
        # zeros filler for experts with no routed tokens this step: an
        # unrouted expert's grouped-GEMM rows are all-zero inputs whose
        # outputs are never gathered back, so substituting zero weights is
        # bit-identical (zeros — NOT uninitialized memory — so no NaNs
        # propagate through the masked-out rows).  Built EAGERLY: the first
        # acquire_experts happens inside a decode region where allocating
        # would trip the transfer guard.
        self._zero_expert: Optional[Tuple] = None
        if self._experts_host:
            self._zeros_expert()

    def _pin(self, tree: Dict) -> Dict:
        """Copy a resident module to the device (a no-op for arrays that
        already live there); a mesh store shards expert stacks over the
        model axis."""
        def put(path, a):
            if (self._expert_sharding is not None
                    and "experts" in jax.tree_util.keystr(path)):
                return jax.device_put(a, self._expert_sharding)
            return jax.device_put(a, self.device)

        return jax.tree_util.tree_map_with_path(put, tree)

    @classmethod
    def build(
        cls,
        cfg: ModelConfig,
        params: Dict,
        plan,
        stream_weights: bool = False,
        resident_bytes: Optional[float] = None,
        prefetch: bool = True,
        predict_topk: Optional[int] = None,
        lru_bytes: Optional[float] = None,
        device=None,
        sctx=None,
    ) -> "ParamStore":
        """THE budget-resolution policy, shared by the engine constructor
        and the scheduler: everything resident unless ``stream_weights``;
        the budget is the plan's ``s_params`` unless ``resident_bytes``
        overrides it.  Predictive per-expert streaming follows the plan's
        ``predict_topk`` unless overridden."""
        budget = None
        khat = 0
        if stream_weights:
            budget = plan.s_params if resident_bytes is None else resident_bytes
            khat = (
                getattr(plan, "predict_topk", 0)
                if predict_topk is None else predict_topk
            )
        return cls(
            cfg, params, resident_bytes=budget, prefetch=prefetch,
            predict_topk=khat, lru_bytes=lru_bytes, device=device, sctx=sctx,
        )

    # -- residency inspection -------------------------------------------
    @property
    def fully_resident(self) -> bool:
        """True when every per-layer module is device-pinned — the
        precondition for the engine's fused decode path (one donated launch
        needs every layer's weights alive on device at once; streamed layers
        keep the per-layer dispatch loop so the htod prefetch has a layer
        boundary to hide behind)."""
        return all(not h for h in self._host) and not self._experts_host

    def fused_layer_params(self) -> Tuple[Dict, ...]:
        """Per-layer merged param dicts for the fused decode macro-step.

        Only meaningful when ``fully_resident`` — the returned tuple aliases
        the device-pinned arrays (no copies) and is captured once by the
        engine for the lifetime of the store."""
        assert self.fully_resident, "fused params require full residency"
        return tuple(self.acquire(li) for li in range(len(self.schema)))

    def resident_module_bytes(self) -> int:
        return (
            _tree_bytes(self.base) + _tree_bytes(self.lead)
            + sum(_tree_bytes(m) for res in self._resident
                  for m in res.values())
            + sum(_tree_bytes(m) for m in self._moe_shared.values())
        )

    def streamed_module_bytes(self) -> int:
        return (
            sum(_tree_bytes(m) for h in self._host for m in h.values())
            + sum(_tree_bytes(m) for m in self._experts_host.values())
        )

    def describe(self) -> str:
        pred = (
            f", predict_topk={self.predict_topk}, "
            f"lru={self.lru_bytes / 1e9:.3f}GB"
            if self.predict_topk > 0 else ""
        )
        return (
            f"resident {self.resident_module_bytes() / 1e9:.3f}GB "
            f"(+{self.residency.n_streamed()} streamed modules, "
            f"{self.streamed_module_bytes() / 1e9:.3f}GB host-side, "
            f"window={self.prefetch_depth}, "
            f"prefetch={'on' if self.prefetch_enabled else 'off'}{pred})"
        )

    # -- streaming -------------------------------------------------------
    # window-facing views kept for callers/tests that inspect the store
    @property
    def _inflight(self) -> Dict[int, Tuple[Dict[str, Dict], int]]:
        return self._window.inflight

    @property
    def htod_bytes(self) -> int:
        return self._window.htod_bytes + self._expert_window.htod_bytes

    @property
    def prefetch_wait_s(self) -> float:
        return self._window.wait_s + self._expert_window.wait_s

    @property
    def prefetch_issued(self) -> int:
        return self._window.issued + self._expert_window.issued

    @property
    def demand_fetches(self) -> int:
        return self._window.demand + self._expert_window.demand

    def _fetch(self, li: int) -> Tuple[Dict[str, Dict], int]:
        """Issue the async htod copy of layer ``li``'s streamed modules."""
        fetched = {
            name: jax.device_put(tree, self.device)
            for name, tree in self._host[li].items()
        }
        nbytes = sum(_tree_bytes(tree) for tree in fetched.values())
        return fetched, nbytes

    def prefetch(self, li: int) -> None:
        """Stage layer ``li``'s streamed modules into the in-flight window
        (async; returns immediately).  Call right after ``acquire(li - 1)``
        returns, before launching that layer's stages, so the copy hides
        behind their dispatch and compute; never before: the window would
        then hold a third layer.  Wraps module indices, so the last layer
        prefetches layer 0 for the next decode step.  Returns at once when
        layer ``li`` has no host-side modules."""
        if not self.prefetch_enabled:
            return
        li = li % len(self.schema)
        if not self._host[li]:
            return
        self._window.prefetch(li)

    def acquire(self, li: int, experts: bool = True) -> Dict:
        """Return layer ``li``'s full param dict with streamed modules on
        device, consuming the in-flight prefetch (or fetching on demand).
        The time spent waiting on the transfer — ideally ~0 when prefetch
        overlapped it with compute — is accounted in ``prefetch_wait_s``.

        For predictive-streamed MoE layers, ``experts=False`` returns only
        the mixer + pinned norm2/router — the decode hot path assembles the
        expert stacks itself via ``acquire_experts`` after reading back the
        routed set.  ``experts=True`` (prefill) assembles the
        FULL expert stack, bit-identical to whole-stack streaming."""
        merged: Dict = {}
        for tree in self._resident[li].values():
            merged.update(tree)
        if self._host[li]:
            for tree in self._window.acquire(li).values():
                merged.update(tree)
        if li in self._moe_shared:
            shared = self._moe_shared[li]
            merged["norm2"] = shared["norm2"]
            moe: Dict = {k: v for k, v in shared.items() if k != "norm2"}
            if experts:
                wg, wu, wd = self.acquire_experts(
                    li, range(self.cfg.num_experts), record=False
                )
                moe["experts_w_gate"] = wg
                moe["experts_w_up"] = wu
                moe["experts_w_down"] = wd
            merged["moe"] = moe
        return merged

    # -- predictive per-expert streaming --------------------------------
    def streams_experts(self, li: int) -> bool:
        """True when layer ``li``'s expert stacks stream per-expert (the
        predictive decode stage applies)."""
        return li % len(self.schema) in self._experts_host

    def moe_shared(self, li: int) -> Dict:
        """Device-pinned norm2 + router of a predictive-streamed MoE layer
        — the router is what lets layer *l* predict layer *l+1*'s experts
        without waiting for *l+1*'s weights."""
        return self._moe_shared[li % len(self.schema)]

    def _fetch_expert(self, key: Tuple[int, int]) -> Tuple[Tuple, int]:
        """Issue the async htod copy of ONE expert's weight slices."""
        li, e = key
        host = self._experts_host[li]
        tree = tuple(
            jax.device_put(host[k][e], self.device)
            for k in ("experts_w_gate", "experts_w_up", "experts_w_down")
        )
        return tree, _tree_bytes(tree)

    def _zeros_expert(self) -> Tuple:
        """Cached zero-weight filler for experts with no routed tokens.
        Zero weights are exact for unrouted experts (their buffer rows are
        never gathered back) and, unlike uninitialized memory, cannot leak
        NaNs through the masked scatter."""
        if self._zero_expert is None:
            host = next(iter(self._experts_host.values()))
            self._zero_expert = tuple(
                jnp.zeros(host[k].shape[1:], dtype=host[k].dtype,
                          device=self.device)
                for k in ("experts_w_gate", "experts_w_up", "experts_w_down")
            )
        return self._zero_expert

    def _lru_get(self, key: Tuple[int, int]) -> Optional[Tuple]:
        hit = self._lru.get(key)
        if hit is None:
            return None
        self._lru.move_to_end(key)
        return hit[0]

    def _lru_put(self, key: Tuple[int, int], tree: Tuple, nbytes: int) -> None:
        """Promote a just-used expert into the hot-expert LRU; demote the
        coldest entries past the byte budget.  Promotion on every use makes
        residency track measured routing frequency: hot experts stay, cold
        ones age out."""
        if nbytes > self.lru_bytes:
            return
        if key in self._lru:
            self._lru.move_to_end(key)
            return
        self._lru[key] = (tree, nbytes)
        self._lru_used += nbytes
        while self._lru_used > self.lru_bytes and self._lru:
            _, (_, old_bytes) = self._lru.popitem(last=False)
            self._lru_used -= old_bytes

    def prefetch_experts(self, li: int, expert_ids: Iterable[int]) -> None:
        """Stage the PREDICTED expert set for layer ``li`` into the
        expert window (async).  Experts already hot in the LRU skip the
        copy entirely — that is the LRU paying for itself."""
        if not self.prefetch_enabled:
            return
        li = li % len(self.schema)
        if li not in self._experts_host:
            return
        E = self.cfg.num_experts
        for e in expert_ids:
            e = int(e)
            if not 0 <= e < E or (li, e) in self._lru:
                continue
            self._expert_window.prefetch((li, e))

    def acquire_experts(
        self, li: int, expert_ids: Iterable[int], record: bool = True
    ) -> Tuple:
        """Assemble layer ``li``'s grouped-GEMM weight stacks (E, ...) with
        true weights for ``expert_ids`` and the zeros filler elsewhere.

        Source order per expert: hot-expert LRU -> in-flight predicted
        prefetch -> on-demand fetch (the guaranteed-correct misprediction
        fallback).  ``record=True`` (the decode stage) counts
        prediction/LRU hit accounting; prefill's all-expert assembly passes
        ``record=False`` so it cannot dilute the decode hit rate."""
        li = li % len(self.schema)
        want = {int(e) for e in expert_ids}
        zeros = self._zeros_expert()
        cols: List[Tuple] = []
        for e in range(self.cfg.num_experts):
            if e not in want:
                cols.append(zeros)
                continue
            key = (li, e)
            tree = self._lru_get(key)
            if tree is not None:
                if record:
                    self._expert_counters["lru_hits"] += 1
                cols.append(tree)
                continue
            staged = key in self._expert_window.inflight
            if record:
                which = "pred_hits" if staged else "pred_misses"
                self._expert_counters[which] += 1
            tree = self._expert_window.acquire(key)
            self._lru_put(key, tree, _tree_bytes(tree))
            cols.append(tree)
        return tuple(jnp.stack([c[i] for c in cols]) for i in range(3))

    def take_counters(self) -> Tuple[int, float]:
        """Drain (htod_bytes, prefetch_wait_s) since the last call —
        summed over the whole-module and per-expert windows."""
        b1, w1 = self._window.take_counters()
        b2, w2 = self._expert_window.take_counters()
        return b1 + b2, w1 + w2

    def take_fault_counters(self) -> Tuple[int, int]:
        """Drain (transfer retries, watchdog timeouts) since the last call
        — summed over the whole-module and per-expert windows."""
        r1, t1 = self._window.take_fault_counters()
        r2, t2 = self._expert_window.take_fault_counters()
        return r1 + r2, t1 + t2

    def take_expert_counters(self) -> Dict[str, int]:
        """Drain predictive-streaming hit counters since the last call:
        ``pred_hits`` (expert was staged by prediction), ``pred_misses``
        (demand-fetched mispredictions/cold starts), ``lru_hits`` (served
        from the hot-expert LRU, no copy at all)."""
        out = dict(self._expert_counters)
        out["lru_bytes_used"] = int(self._lru_used)
        for k in self._expert_counters:
            self._expert_counters[k] = 0
        return out
