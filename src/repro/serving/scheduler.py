"""Back-compat scheduling surface over ``serving.server.Server``.

The scheduler core lives in ``repro.serving.server``: one step-driven loop
(``Server.step``) under two admission policies — ``static`` accumulated
waves (paper §5.1) and ``continuous`` in-flight batching — with
per-request ``SamplingParams``, open-loop arrivals, and request-lifecycle
metrics (TTFT / TPOT / queue wait).  This module re-exports the request
and report types from there and keeps ``serve_dataset`` as a thin
offline-protocol wrapper so existing callers and tests are untouched.
"""
from __future__ import annotations

from typing import List, Optional

from repro.configs.base import ModelConfig
from repro.core.dag_builder import Plan
from repro.core.hardware import HardwareProfile
from repro.serving.sampling import SamplingParams  # noqa: F401  (re-export)
from repro.serving.server import (  # noqa: F401  (re-exports)
    BatchResult,
    Request,
    RequestHandle,
    RequestResult,
    ServeConfig,
    Server,
    ServeReport,
    StreamConfig,
    pad_requests,
)
from repro.serving.weights import ParamStore

__all__ = [
    "BatchResult", "Request", "RequestHandle", "RequestResult",
    "SamplingParams", "ServeConfig", "Server", "ServeReport", "StreamConfig",
    "pad_requests", "serve_dataset",
]


def serve_dataset(
    cfg: ModelConfig,
    params,
    requests: List[Request],
    plan: Plan,
    decode_len: int,
    max_seq: Optional[int] = None,
    scheduler: str = "static",
    pad_id: int = 0,
    eos_id: Optional[int] = None,
    max_prompt_len: Optional[int] = None,
    grouped_prefill: bool = True,
    stream_weights: bool = False,
    resident_bytes: Optional[float] = None,
    prefetch: bool = True,
    hw: Optional[HardwareProfile] = None,
    store: Optional[ParamStore] = None,
    kv_page_tokens: int = 0,
    device_kv_gb: Optional[float] = None,
    prefix_cache: bool = False,
    sctx=None,
    ep_chunks: int = 1,
    faults=None,
) -> ServeReport:
    """Serve a fixed request list to completion (the offline protocol).

    .. deprecated::
        ``serve_dataset`` is a back-compat wrapper over
        ``repro.serving.server.Server`` — new code should build a
        ``Server`` with ``ServeConfig`` / ``StreamConfig`` and use
        ``submit`` / ``step`` / ``run`` directly, which also opens online
        arrivals (``Request.arrival_s``), per-request sampling
        (``Request.sampling``), and streaming token callbacks.

    ``scheduler`` selects static accumulated waves vs continuous in-flight
    batching.  Per-request ``decode_len`` is honored (``decode_len`` is the
    fallback for requests with a zero/None field); ``eos_id`` finishes a
    sequence early.

    ``stream_weights=True`` executes through the streamed parameter store:
    only the greedy ``resident_bytes`` set (default ``plan.s_params``) is
    pinned on device, the rest is served through the engine's
    double-buffered async prefetch (``prefetch=False`` degrades to
    serialized fetches); transfer accounting lands in
    ``ServeReport.htod_gb`` / ``prefetch_wait_s``.  A pre-built ``store``
    overrides the residency arguments (one store is always shared by every
    engine the scheduler creates).

    ``sctx`` (a mesh ``ShardCtx`` with ``moe_dispatch`` 'a2a'/'psum') runs
    the engine expert-parallel across the mesh's model axis; ``ep_chunks``
    picks the pipelined all-to-all chunk count (``repro.distributed``).

    ``hw`` enables memory-aware admission in the continuous scheduler:
    a queued request is admitted only while every in-flight sequence's
    offloaded KV/state (at its full prompt+decode extent) fits the Eq. 2
    host budget (``m_c - S_Model``) — over-long prompts wait instead of
    overflowing host memory (``ServeReport.admission_deferrals`` counts the
    waits).  A request that could never fit raises ``ValueError``.

    ``faults`` arms a deterministic fault-injection plan for the run (a
    ``repro.faults.FaultPlan`` / ``FaultSpec`` / spec string — see
    ``ServeConfig.faults``); ``None`` leaves serving byte-identical to an
    unarmed build.
    """
    assert scheduler in ("static", "continuous"), scheduler
    if not requests:
        return ServeReport(scheduler=scheduler)
    server = Server(
        cfg, params, plan,
        serve=ServeConfig(
            scheduler=scheduler, decode_len=decode_len, max_seq=max_seq,
            max_prompt_len=max_prompt_len, pad_id=pad_id, eos_id=eos_id,
            grouped_prefill=grouped_prefill, hw=hw,
            kv_page_tokens=kv_page_tokens, device_kv_gb=device_kv_gb,
            prefix_cache=prefix_cache, sctx=sctx, ep_chunks=ep_chunks,
            faults=faults,
        ),
        stream=StreamConfig(
            stream_weights=stream_weights, resident_bytes=resident_bytes,
            prefetch=prefetch,
        ),
        store=store,
    )
    for r in requests:
        server.submit(r)
    return server.run()
