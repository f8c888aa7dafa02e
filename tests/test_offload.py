"""Offload modes through the session: BINARY vs ROI contracts, workload
registration, sub-region submits, and the per-phase breakdown."""
import time

import numpy as np
import pytest

from repro.api import (EngineSession, OffloadMode, PhaseBreakdown, Region,
                       coexec)
from repro.core import programs as P
from repro.core.device import DeviceGroup

GAUSS2D_KW = dict(h=128, w=96, lws=(16, 8))


def devices3():
    return [DeviceGroup("cpu", throttle=3.0),
            DeviceGroup("igpu", throttle=1.5),
            DeviceGroup("gpu", throttle=1.0)]


@pytest.fixture(scope="module")
def gauss2d_ref():
    return P.reference_output("gaussian2d", **GAUSS2D_KW)


# ----------------------------------------------------------- 2-D programs

def test_2d_program_full_region_exact(gauss2d_ref):
    res = coexec(P.PROGRAMS["gaussian2d"](**GAUSS2D_KW), devices3())
    assert res.output.shape == (128, 96)
    np.testing.assert_allclose(res.output, gauss2d_ref,
                               rtol=1e-5, atol=1e-5)
    for p in res.packets:
        assert p.region is not None and p.region.ndim == 2


def test_2d_ray_program_exact():
    ref = P.reference_output("ray1_2d", px=64)
    res = coexec(P.PROGRAMS["ray1_2d"](px=64), devices3())
    assert res.output.shape == (64, 64 * 3)
    np.testing.assert_allclose(res.output, ref, rtol=1e-5, atol=1e-5)


def test_2d_mandelbrot_roi_matches_full_slice():
    px = 64
    ref = P.reference_output("mandelbrot2d", px=px)
    prog = P.PROGRAMS["mandelbrot2d"](px=px)
    roi = Region.rect(16, 24, lws=(8, 8), offset=(8, 16))
    res = coexec(prog, devices3(), region=roi)
    assert res.output.shape == (16, 24)
    np.testing.assert_array_equal(res.output, ref[8:24, 16:40])


# ------------------------------------------------------------- ROI mode

def test_roi_submits_reuse_registered_workload(gauss2d_ref):
    prog = P.PROGRAMS["gaussian2d"](**GAUSS2D_KW)
    roi = Region.rect(32, 48, lws=(16, 8), offset=(16, 8))
    with EngineSession(devices3(), init_cost_s=0.05) as session:
        session.register_workload(prog)
        assert session.init_payments == 3       # init paid at registration
        assert "gaussian2d" in session.workloads
        for _ in range(3):                      # warm back-to-back submits
            r = session.submit(prog, region=roi,
                               mode=OffloadMode.ROI).result()
            np.testing.assert_allclose(r.output, gauss2d_ref[16:48, 8:56],
                                       rtol=1e-5, atol=1e-5)
        assert session.init_payments == 3       # nothing rebuilt
        assert all(v == 1 for v in session.buffer_registry.values())
        session.unregister_workload("gaussian2d")
        assert "gaussian2d" not in session.workloads
        assert session.executables == {}


def test_roi_requires_registration():
    prog = P.PROGRAMS["gaussian2d"](**GAUSS2D_KW)
    with EngineSession(devices3()) as session:
        with pytest.raises(RuntimeError, match="register_workload"):
            session.submit(prog, mode=OffloadMode.ROI)


def test_region_validation_errors():
    prog = P.PROGRAMS["gaussian2d"](**GAUSS2D_KW)
    with EngineSession(devices3()) as session:
        with pytest.raises(ValueError, match="not contained"):
            session.submit(prog, region=Region.rect(256, 96, lws=(16, 8)))
        with pytest.raises(ValueError, match="lws-aligned"):
            session.submit(prog, region=Region.rect(16, 8, lws=(16, 8),
                                                    offset=(8, 8)))
        with pytest.raises(ValueError, match="dims"):
            session.submit(prog, region=Region.line(16))


def test_roi_1d_subregion(gauss2d_ref):
    """1-D programs accept line sub-regions too (offset in work-groups)."""
    kw = dict(h=256, w=64)
    prog = P.PROGRAMS["gaussian"](**kw)
    ref = P.reference_output("gaussian", **kw)
    lws_rows = P.gaussian_ops.LWS               # rows per work-group
    res = coexec(prog, devices3(), region=Region.line(1, offset=1))
    np.testing.assert_allclose(res.output, ref[lws_rows:2 * lws_rows],
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------- BINARY mode

def test_binary_mode_pays_init_every_submit_and_evicts():
    prog = P.PROGRAMS["gaussian2d"](**GAUSS2D_KW)
    with EngineSession(devices3(), init_cost_s=0.02) as session:
        for k in (1, 2):
            r = session.submit(prog, mode=OffloadMode.BINARY).result()
            assert session.init_payments == 3 * k   # fresh build per submit
            assert session.executables == {}        # torn down after
            assert r.phases is not None
            # init phase charges the emulated driver cost to THIS run
            assert r.phases.init_s >= 0.02


def test_binary_refuses_registered_workload_then_evicts_plain_cache():
    prog = P.PROGRAMS["gaussian2d"](**GAUSS2D_KW)
    with EngineSession(devices3()) as session:
        session.register_workload(prog)
        # refusing protects the ROI contract: a BINARY teardown would
        # silently de-warm subsequent ROI submits
        with pytest.raises(ValueError, match="unregister_workload"):
            session.submit(prog, mode=OffloadMode.BINARY)
        session.unregister_workload("gaussian2d")
        session.run(prog)                           # plain cached submit
        assert len(session.executables) == 3
        session.submit(prog, mode=OffloadMode.BINARY).result()
        assert session.executables == {}            # teardown dropped it


# BINARY outputs come from the session's free list: a dropped output is
# written again in place, a held one never is
BINARY_1D = {"gaussian": dict(h=1024, w=128),
             "mandelbrot": dict(px=64, max_iter=64)}


@pytest.fixture(scope="module")
def binary_refs():
    return {name: P.reference_output(name, **kw)
            for name, kw in BINARY_1D.items()}


def _binary(session, name):
    prog = P.PROGRAMS[name](**BINARY_1D[name])
    return session.submit(prog, mode=OffloadMode.BINARY).result()


def _scribble(out):
    """Dirty a dropped output, so a rerun must rewrite every row."""
    out.view(np.uint8)[...] = 0xA5
    return out.ctypes.data


def wait_idle(session, timeout=10.0):
    """Wait until a dropped output is idle: the session's runner thread
    lets go of the result a moment after ``result()`` returned."""
    t_end = time.monotonic() + timeout
    while session.output_stats.idle == 0:
        assert time.monotonic() < t_end, "a dropped output never went idle"
        time.sleep(1e-3)


@pytest.mark.parametrize("name", sorted(BINARY_1D))
def test_binary_submit_writes_into_a_dropped_output(name, binary_refs):
    with EngineSession(devices3()) as session:
        first = _binary(session, name)
        assert not first.output_reused
        where = _scribble(first.output)
        del first
        wait_idle(session)
        second = _binary(session, name)
        assert second.output_reused
        assert second.output.ctypes.data == where
        np.testing.assert_array_equal(second.output, binary_refs[name])
        assert session.executables == {}        # teardown still evicts
        s = session.output_stats
        assert (s.hits, s.misses) == (1, 1)


@pytest.mark.parametrize("name", sorted(BINARY_1D))
def test_binary_submit_never_writes_into_a_held_output(name, binary_refs):
    with EngineSession(devices3()) as session:
        first = _binary(session, name)
        before = first.output.copy()
        second = _binary(session, name)
        assert not second.output_reused
        assert not np.shares_memory(first.output, second.output)
        assert first.output.tobytes() == before.tobytes()
        np.testing.assert_array_equal(second.output, binary_refs[name])
        rows = first.output[8:16]               # a slice alone still holds
        del first
        third = _binary(session, name)
        assert not third.output_reused
        assert rows.tobytes() == before[8:16].tobytes()


@pytest.mark.parametrize("name", sorted(BINARY_1D))
def test_device_death_on_a_reused_dirty_output_stays_exact(name,
                                                            binary_refs):
    devs = [DeviceGroup("flaky", throttle=1.5, fail_after=0),
            DeviceGroup("igpu", throttle=2.0),
            DeviceGroup("gpu", throttle=1.0)]
    with EngineSession(devs, scheduler="static") as session:
        where = _scribble(_binary(session, name).output)
        wait_idle(session)
        res = _binary(session, name)
        assert res.output_reused and res.output.ctypes.data == where
        assert res.aborted_devices == 1 and res.retries >= 1
        np.testing.assert_array_equal(res.output, binary_refs[name])


def test_roi_rejects_different_instance_under_same_name():
    prog = P.PROGRAMS["gaussian2d"](**GAUSS2D_KW)
    impostor = P.PROGRAMS["gaussian2d"](**GAUSS2D_KW)   # same name, new data
    with EngineSession(devices3()) as session:
        session.register_workload(prog)
        with pytest.raises(ValueError, match="different program instance"):
            session.submit(impostor, mode=OffloadMode.ROI)


# ------------------------------------------------------ phase breakdown

def test_phase_breakdown_identity(gauss2d_ref):
    res = coexec(P.PROGRAMS["gaussian2d"](**GAUSS2D_KW), devices3(),
                 init_cost_s=0.03)
    ph = res.phases
    assert isinstance(ph, PhaseBreakdown)
    assert ph.roi_s == res.total_time
    assert ph.offload_s >= ph.roi_s
    assert ph.init_s >= 0.03                    # compiles inside init phase
    assert res.binary_time == pytest.approx(ph.binary, rel=1e-6)
    assert ph.management == pytest.approx(ph.binary - ph.roi_s, rel=1e-6)


def test_roi_warm_submits_beat_binary(gauss2d_ref):
    """The paper's asymmetry, as a coarse invariant at test scale: a warm
    ROI submit must not pay the per-run init a BINARY submit pays."""
    prog = P.PROGRAMS["gaussian2d"](**GAUSS2D_KW)
    roi = Region.rect(64, 96, lws=(16, 8), offset=(32, 0))
    with EngineSession(devices3(), init_cost_s=0.1) as session:
        session.register_workload(prog)
        session.submit(prog, region=roi, mode=OffloadMode.ROI).result()
        warm = session.submit(prog, region=roi,
                              mode=OffloadMode.ROI).result()
        session.unregister_workload(prog.name)
        cold = session.submit(prog, region=roi,
                              mode=OffloadMode.BINARY).result()
    assert warm.phases.init_s < 0.1             # no driver cost re-paid
    assert cold.phases.init_s >= 0.1
    assert cold.phases.binary > warm.phases.binary


def test_simulator_fills_phases():
    from repro.core.simulate import SimConfig, simulate, SimDevice
    devs = [SimDevice("gpu", throughput=1000.0),
            SimDevice("cpu", throughput=250.0)]
    r = simulate(4096, 8, devs, SimConfig())
    assert r.phases is not None
    assert r.phases.roi_s == r.total_time
    assert r.binary_time == pytest.approx(r.phases.binary)
