"""Pilot-Abstraction behaviour: pilots, CUs, DUs, tiers, affinity scheduling,
late binding, retained-executable cache, MapReduce, KMeans."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ComputeDataManager, ComputeUnitDescription, DataUnit,
                        PilotComputeDescription, PilotComputeService, State,
                        kmeans, make_backend, make_blobs, map_reduce)
from repro.core.backends.base import register_backend
from repro.core.backends.simulated import FaultPolicy, SimulatedClusterBackend


@pytest.fixture
def service():
    svc = PilotComputeService()
    yield svc
    svc.cancel_all()


@pytest.fixture
def backends(tmp_path):
    return {"file": make_backend("file", root=tmp_path / "file"),
            "object": make_backend("object", root=tmp_path / "obj"),
            "host": make_backend("host"),
            "device": make_backend("device")}


def test_pilot_lifecycle_and_cu(service):
    pilot = service.submit_pilot(PilotComputeDescription(backend="inprocess"))
    assert pilot.state == State.RUNNING
    manager = ComputeDataManager(service)
    cu = manager.run(lambda x: x * 2, 21)
    assert cu.result() == 42
    assert cu.state == State.DONE
    assert cu.pilot_id == pilot.id


def test_cu_failure_surfaces_exception(service):
    service.submit_pilot(PilotComputeDescription(backend="inprocess"))
    manager = ComputeDataManager(service)
    cu = manager.run(lambda: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        cu.result()
    assert cu.state == State.FAILED


def test_late_binding_waits_for_pilot(service):
    """CU submitted before any pilot exists binds once one appears."""
    manager = ComputeDataManager(service)
    import threading
    out = {}

    def submit():
        out["cu"] = manager.run(lambda: "late")

    t = threading.Thread(target=submit)
    t.start()
    time.sleep(0.1)
    service.submit_pilot(PilotComputeDescription(backend="inprocess"))
    t.join(5)
    assert out["cu"].result(10) == "late"


def test_data_unit_tier_staging(backends):
    arr = np.arange(4000, dtype=np.float32).reshape(500, 8)
    du = DataUnit.from_array("x", arr, 4, backends, tier="file")
    for tier in ("host", "device", "host", "file"):
        du.to_tier(tier)
        np.testing.assert_array_equal(
            np.concatenate(list(du.partitions())), arr)


def test_affinity_scheduling_prefers_matching_pilot(service):
    p_a = service.submit_pilot(PilotComputeDescription(
        backend="inprocess", affinity="rack-a"))
    p_b = service.submit_pilot(PilotComputeDescription(
        backend="inprocess", affinity="rack-b"))
    manager = ComputeDataManager(service)
    desc = ComputeUnitDescription(fn=lambda: 0, affinity="rack-b")
    chosen = manager.select_pilot(desc)
    assert chosen.id == p_b.id


def test_device_residency_dominates_scheduling(service, backends):
    p_busy = service.submit_pilot(PilotComputeDescription(
        backend="inprocess", affinity="busy"))
    p_other = service.submit_pilot(PilotComputeDescription(
        backend="inprocess", affinity="other"))
    manager = ComputeDataManager(service)
    pts, _ = make_blobs(1000, 4, d=4)
    du = DataUnit.from_array("pts", pts, 2, backends, tier="device")
    desc = ComputeUnitDescription(fn=lambda: 0, input_data=(du,),
                                  affinity="other")
    s_busy = manager.score(p_busy, desc)
    desc_no_data = ComputeUnitDescription(fn=lambda: 0, affinity="other")
    assert s_busy > manager.score(p_busy, desc_no_data)


def test_retained_jit_cache_warm_start(service):
    pilot = service.submit_pilot(PilotComputeDescription(backend="inprocess"))
    calls = []

    def build():
        calls.append(1)
        import jax
        return jax.jit(lambda x: x + 1)

    f1 = pilot.jit_cached("inc", build)
    f2 = pilot.jit_cached("inc", build)
    assert f1 is f2 and len(calls) == 1


def test_map_reduce_tier_equivalence(backends, service):
    service.submit_pilot(PilotComputeDescription(backend="inprocess"))
    manager = ComputeDataManager(service)
    pts = np.random.default_rng(0).normal(size=(1024, 4)).astype(np.float32)
    results = {}
    for tier in ("file", "host", "device"):
        du = DataUnit.from_array(f"mr-{tier}", pts, 4, backends, tier=tier)
        results[tier] = float(map_reduce(
            du, lambda p: jnp.sum(p.astype(jnp.float32)), lambda a, b: a + b,
            manager=manager))
    ref = float(pts.sum())
    for tier, val in results.items():
        assert abs(val - ref) < 1e-1 * abs(ref) + 1e-3, (tier, val, ref)


def test_kmeans_backend_equivalence_and_speedup_direction(backends, service):
    """The paper's Fig. 9 structure: same SSE across backends; memory tiers
    not slower than the (simulated-throttled) file tier."""
    from repro.core.memory import PROFILES, FileBackend
    pts, _ = make_blobs(20_000, 10, d=8, seed=1)
    slow_file = {"file": FileBackend(backends["file"].root / "slow",
                                     PROFILES["stampede_disk"]),
                 "host": backends["host"], "device": backends["device"]}
    du_file = DataUnit.from_array("kf", pts, 4, slow_file, tier="file")
    du_dev = DataUnit.from_array("kd", pts, 4, backends, tier="device")
    pilot = service.submit_pilot(PilotComputeDescription(backend="inprocess"))
    manager = ComputeDataManager(service)
    r_file = kmeans(du_file, k=8, iters=4, manager=manager)
    r_dev = kmeans(du_dev, k=8, iters=4, pilot=pilot)
    np.testing.assert_allclose(r_file.sse_history[-1], r_dev.sse_history[-1],
                               rtol=1e-3)
    # compare steady-state iterations (iter 0 is compile-dominated for both)
    assert (np.mean(r_dev.iter_seconds[1:]) < np.mean(r_file.iter_seconds[1:]))


def test_simulated_pilot_failure_and_manager_retry(service):
    register_backend(SimulatedClusterBackend(
        substrate="yarn",
        policy=FaultPolicy(fail_cu_ids=frozenset({"will-fail"}))))
    service.submit_pilot(PilotComputeDescription(backend="simulated"))
    manager = ComputeDataManager(service)
    desc = ComputeUnitDescription(fn=lambda: "ok", name="will-fail")
    assert manager.result_with_retry(desc, retries=2) == "ok"


def test_pilot_loss_recovery_via_retry(service):
    register_backend(SimulatedClusterBackend(
        substrate="slurm", policy=FaultPolicy(fail_devices_at=2)))
    dying = service.submit_pilot(PilotComputeDescription(backend="simulated"))
    manager = ComputeDataManager(service)
    for i in range(2):
        manager.run(lambda i=i: i).result()
    # pilot now dies; healthy inprocess pilot takes over via late binding
    service.submit_pilot(PilotComputeDescription(backend="inprocess"))
    out = manager.result_with_retry(
        ComputeUnitDescription(fn=lambda: "survived"), retries=3)
    assert out == "survived"
