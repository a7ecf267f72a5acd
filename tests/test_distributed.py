"""Distributed serving: expert-parallel mesh engine + replica server.

The mesh decode contract is the hypothesis-style property at the heart of
the subsystem (tested WITHOUT importing hypothesis, which this environment
does not ship): across random ragged workloads, expert-parallel degrees
and both schedulers, serving on a ``(1, ep)`` mesh generates tokens
IDENTICAL to the single-device engine — distribution moves WHERE experts
run, never WHICH tokens come out.  Device count locks at first backend
init, so every mesh case runs in a child interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
``test_multidevice`` pattern); the sanitizer-strict serve rides in the
same subprocess.

The replica server, the engine-construction validation and the pure
helpers run in-process (no mesh required).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["REPRO_SANITIZE"] = "strict"
    import jax
    import numpy as np
    from repro import analysis
    from repro.configs import get_config
    from repro.core.dag_builder import Plan
    from repro.models import model as M
    from repro.serving.server import (
        Request, ServeConfig, Server, StreamConfig,
    )
    from repro.launch.mesh import make_mesh
    from repro.sharding.specs import ShardCtx

    assert len(jax.devices()) == 8, jax.devices()
    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    plan = Plan(B=8, b_a=8, b_e=64, decode_chunk=4)
    rng = np.random.default_rng(0)

    # 8 requests fill the static wave to the full B=8, so the decode batch
    # divides every ep degree (the indivisible case is checked below)
    def workload(trial):
        lens = rng.integers(3, 17, size=8)
        reqs = [
            Request(
                prompt=rng.integers(
                    1, cfg.vocab_size, size=int(s)
                ).astype(np.int32),
                decode_len=int(rng.integers(2, 8)),
            )
            for s in lens
        ]
        return reqs

    def serve(reqs, scheduler, sctx=None, ep_chunks=1):
        sv = Server(
            cfg, params, plan,
            ServeConfig(scheduler=scheduler, sctx=sctx,
                        ep_chunks=ep_chunks),
            StreamConfig(),
        )
        for r in reqs:
            sv.submit(r)
        rep = sv.run()
        toks = [rr.tokens.tolist() for rr in rep.request_results]
        return rep, toks

    meshes = {
        ep: ShardCtx(
            mesh=make_mesh((1, ep), ("data", "model")),
            batch_axes=("data",), model_axis="model", moe_dispatch="a2a",
        )
        for ep in (1, 2, 4)
    }
    for trial in range(2):
        reqs = workload(trial)
        for scheduler in ("static", "continuous"):
            _, want = serve(reqs, scheduler)
            for ep, sctx in meshes.items():
                rep, got = serve(reqs, scheduler, sctx=sctx, ep_chunks=2)
                assert got == want, (trial, scheduler, ep, got, want)
                if ep > 1:
                    assert rep.a2a_bytes > 0, (trial, scheduler, ep)
                    assert rep.collective_dispatches > 0

    # a batch that doesn't divide the mesh (6 rows over 4 ranks) is padded
    # with rows that are never sent: still the collective path, still
    # token-identical
    reqs6 = workload(7)[:6]
    _, want6 = serve(reqs6, "static")
    rep6, got6 = serve(reqs6, "static", sctx=meshes[4])
    assert got6 == want6, (got6, want6)
    assert rep6.a2a_bytes > 0

    # ReplicaServer: one device group per replica, each replica's expert
    # stacks built sharded over its own group
    from repro.distributed import ReplicaServer

    rs = ReplicaServer(cfg, params, 2, plan=plan,
                       serve=ServeConfig(scheduler="static",
                                         sctx=meshes[2]))
    for r in reqs6:
        rs.submit(r)
    got_rs = [rr.tokens.tolist() for rr in rs.run().merged.request_results]
    assert got_rs == want6, (got_rs, want6)
    devs = jax.devices()
    for i, s in enumerate(rs.servers):
        assert list(s.serve.sctx.mesh.devices.flat) == devs[2 * i:2 * i + 2]
        assert s.serve.device == devs[2 * i]
        wg = s._store._resident[0]["ffn"]["moe"]["experts_w_gate"]
        assert {d for d in wg.devices()} == set(devs[2 * i:2 * i + 2])
        assert {sh.data.shape[0] for sh in wg.addressable_shards} == {
            cfg.num_experts // 2}
        assert {d for d in s._store.base["embed"].devices()} == {devs[2 * i]}

    # sanitizer-strict pass over a mesh Server.run(): decode regions run
    # under jax.transfer_guard('disallow'); the mesh batch/combine moves
    # must all land in planned-transfer scopes
    with analysis.sanitize(strict=True, donation=True) as san:
        rep, got = serve(workload(99), "static", sctx=meshes[4],
                         ep_chunks=4)
    # strict mode raises on any unplanned transfer, so reaching here IS
    # the pass; the planned-transfer ledger must show the mesh scopes
    sr = san.report()
    assert any(k.startswith("ep-a2a") for k in sr["planned_transfers"]), sr
    bad = [d for d in sr["donation_checks"] if not d["ok"]]
    assert not bad, bad
    print("DISTRIBUTED_MESH_OK", rep.a2a_bytes)
    """
)


def _run_child(script: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, "-c", script], env=env,
        capture_output=True, text=True, timeout=1500,
    )


def test_mesh_decode_token_identical_property():
    """ep in {1,2,4} x {static,continuous} x random ragged workloads:
    mesh serving is token-for-token the single-device serve, with a
    sanitizer-strict pass over the mesh Server riding along."""
    r = _run_child(MESH_SCRIPT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "DISTRIBUTED_MESH_OK" in r.stdout


# ---------------------------------------------------------------------------
# In-process: replica server + construction validation + pure helpers
# ---------------------------------------------------------------------------
def _smoke_setup():
    import jax

    from repro.configs import get_config
    from repro.core.dag_builder import Plan
    from repro.models import model as M

    cfg = get_config("mixtral-8x7b", smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    plan = Plan(B=8, b_a=8, b_e=64, decode_chunk=4)
    return cfg, params, plan


def _requests(cfg, n=6, seed=0):
    from repro.serving.server import Request

    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt=rng.integers(1, cfg.vocab_size,
                                size=int(rng.integers(3, 12))).astype(
                np.int32),
            decode_len=int(rng.integers(2, 7)),
        )
        for _ in range(n)
    ]


@pytest.mark.parametrize("policy", ["round-robin", "least-loaded"])
def test_replica_server_drains_identically(policy):
    """N replicas behind one queue finish the same tokens as one Server,
    re-indexed to global submission order."""
    from repro.distributed import ReplicaServer
    from repro.serving.server import ServeConfig, Server

    cfg, params, plan = _smoke_setup()
    reqs = _requests(cfg)

    one = Server(cfg, params, plan, ServeConfig(scheduler="static"))
    for r in reqs:
        one.submit(r)
    want = [rr.tokens.tolist() for rr in one.run().request_results]

    rs = ReplicaServer(cfg, params, 2, plan=plan,
                       serve=ServeConfig(scheduler="static"), policy=policy)
    for r in reqs:
        rs.submit(r)
    rep = rs.run()
    got = [rr.tokens.tolist() for rr in rep.merged.request_results]
    assert got == want
    assert [rr.index for rr in rep.merged.request_results] == list(
        range(len(reqs)))
    assert len(rep.per_replica) == 2
    # every request landed on exactly one replica
    assert sum(len(r.request_results) for r in rep.per_replica) == len(reqs)
    # work counters sum, phase times take the parallel max
    assert rep.merged.decode_slot_steps == sum(
        r.decode_slot_steps for r in rep.per_replica)
    assert rep.merged.decode_s == max(r.decode_s for r in rep.per_replica)


def test_replica_server_custom_policy_and_errors():
    from repro.distributed import ReplicaServer
    from repro.serving.server import ServeConfig

    cfg, params, plan = _smoke_setup()
    with pytest.raises(ValueError, match="routing policy"):
        ReplicaServer(cfg, params, 2, plan=plan, policy="zigzag")

    # a callable policy routes every request to replica 1
    rs = ReplicaServer(cfg, params, 2, plan=plan,
                       serve=ServeConfig(scheduler="static"),
                       policy=lambda servers, req: 1)
    for r in _requests(cfg, n=3):
        rs.submit(r)
    rep = rs.run()
    assert len(rep.per_replica[0].request_results) == 0
    assert len(rep.per_replica[1].request_results) == 3


def test_mesh_engine_rejects_unsupported_combos():
    """Clear ValueErrors instead of silent single-device fallbacks."""
    import jax
    from dataclasses import replace

    from repro.core.engine import ModuleBatchingEngine
    from repro.distributed import validate_ep_shard
    from repro.launch.mesh import make_mesh
    from repro.sharding.specs import ShardCtx

    cfg, params, plan = _smoke_setup()
    mesh = make_mesh((1, 1), ("data", "model"))
    sctx = ShardCtx(mesh=mesh, batch_axes=("data",), model_axis="model",
                    moe_dispatch="a2a")

    # the 1x1 mesh composes fine (and must stay token-compatible)
    ModuleBatchingEngine(cfg, params, plan, sctx=sctx)

    with pytest.raises(ValueError, match="predict_topk"):
        ModuleBatchingEngine(cfg, params,
                             replace(plan, predict_topk=2), sctx=sctx)
    with pytest.raises(ValueError, match="moe_dispatch"):
        validate_ep_shard(cfg, replace(sctx, moe_dispatch="grouped"))
    # num_experts % n needs n > 1 to fire — exercised in the mesh
    # subprocess; here check the no-mesh contract instead
    assert validate_ep_shard(cfg, None) == 1


def test_ep_helpers():
    from repro.configs import get_config
    from repro.distributed import a2a_bytes_per_stage, pipeline_chunks

    assert pipeline_chunks(8, 4) == 4
    assert pipeline_chunks(8, 3) == 2      # largest divisor <= requested
    assert pipeline_chunks(7, 4) == 1
    assert pipeline_chunks(8, 100) == 8

    cfg = get_config("mixtral-8x7b", smoke=True)
    assert a2a_bytes_per_stage(cfg, T=8, n_model=1) == 0
    b2 = a2a_bytes_per_stage(cfg, T=8, n_model=2)
    b4 = a2a_bytes_per_stage(cfg, T=8, n_model=4)
    assert b2 > 0 and b4 == 2 * b2         # scales with the rank count
    copies = 8 * cfg.experts_per_token
    assert b2 == copies * 2 * (2 * cfg.d_model * 4 + 4)


def test_planner_mesh_shape_picks_chunks():
    """search_decode(mesh_shape=...) returns an expert-parallel plan whose
    modeled throughput is no worse than serial a2a (chunking only hides
    wire time) and a valid chunk count."""
    from dataclasses import replace

    from repro.configs import get_config
    from repro.core.dag_builder import estimate_decode
    from repro.core.hardware import PROFILES
    from repro.core.planner import search_decode

    cfg = get_config("mixtral-8x7b")
    hw = PROFILES["C1-A5000-256GB"]
    res = search_decode(cfg, hw, ctx=256, mesh_shape=(1, 4))
    assert res.plan.ep_chunks in (1, 2, 4, 8)
    serial = estimate_decode(cfg, hw, replace(res.plan, ep_chunks=1),
                             256, mesh_shape=(1, 4))
    assert res.estimate.throughput >= serial.throughput * (1 - 1e-9)
    # the a2a exchange is on the modeled critical path
    est = estimate_decode(cfg, hw, res.plan, 256, mesh_shape=(1, 4))
    assert est.throughput == pytest.approx(res.estimate.throughput)


def test_hardware_a2a_time():
    from repro.core.hardware import PROFILES

    hw = PROFILES["tpu-v5e"]
    assert hw.a2a_time(1e9, 1) == 0.0
    t2, t4 = hw.a2a_time(1e9, 2), hw.a2a_time(1e9, 4)
    assert 0 < t2 < t4                      # more ranks -> more wire
    assert hw.a2a_time(0.0, 4) == 0.0
    # falls back to the host link when no ICI is profiled
    pcie = PROFILES["C1-A5000-256GB"]
    assert pcie.a2a_time(1e9, 2) > pcie.launch_overhead_s
