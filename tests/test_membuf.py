"""Memory-subsystem tests: BufferArena, TransferPipeline, POOLED runs.

Covers the arena's ring/recycle/LRU contracts (including hypothesis-driven
submit sequences), the output free list's reuse rule, pooled-vs-per-packet
output equality on every registered scheduler, the exact five-window phase
identity, fault tolerance under the pipelined device loop, the simulator's
overlap model, and the close() drain-then-release ordering regression.
"""
import sys
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import (
    BufferPolicy,
    EngineSession,
    OffloadMode,
    available_schedulers,
    coexec,
)
from repro.core import programs as P
from repro.core.device import DeviceGroup
from repro.core.membuf import (BufferArena, OutputFreeList, TransferPipeline,
                               bucket_bytes)
from repro.core.runtime import WorkerPool
from repro.core.simulate import SimConfig, SimDevice, simulate

MANDEL_KW = dict(px=48, max_iter=64, lws=(8, 8))
GAUSS_KW = dict(h=64, w=96, lws=(8, 8))


def devices3():
    return [
        DeviceGroup("cpu", throttle=4.0),
        DeviceGroup("igpu", throttle=2.0),
        DeviceGroup("gpu", throttle=1.0),
    ]


# ------------------------------------------------------------------ arena


def test_bucket_bytes_size_classes():
    assert bucket_bytes(1) == 256
    assert bucket_bytes(256) == 256
    assert bucket_bytes(257) == 512
    assert bucket_bytes(8192) == 8192
    assert bucket_bytes(8193) == 16384


def test_ring_hit_then_recycle():
    arena = BufferArena(ring=2)
    l1 = arena.acquire("p", "host", (16, 16), np.float32)
    l2 = arena.acquire("p", "host", (16, 16), np.float32)
    assert not np.shares_memory(l1.array, l2.array)
    # ring full, both leased: the third acquire recycles the OLDEST lease
    l3 = arena.acquire("p", "host", (16, 16), np.float32)
    assert np.shares_memory(l1.array, l3.array)
    s = arena.stats
    assert s.misses == 2 and s.recycles == 1
    assert s.entries == 2 and s.leases_out == 2


def test_release_makes_free_entry_hit():
    arena = BufferArena(ring=2)
    l1 = arena.acquire("p", "host", (8, 8), np.float32)
    arena.release(l1)
    l2 = arena.acquire("p", "host", (8, 8), np.float32)
    assert np.shares_memory(l1.array, l2.array)
    assert arena.stats.hits == 1


def test_rekey_steals_lru_free_entry_from_same_bucket():
    arena = BufferArena(ring=2)
    l1 = arena.acquire("a", "host", (32,), np.float32)  # 128B -> 256B bucket
    arena.release(l1)
    l2 = arena.acquire("b", "host", (64,), np.uint8)  # same 256B bucket
    assert np.shares_memory(l1.array, l2.array)
    s = arena.stats
    assert s.rekeys == 1 and s.misses == 1


def test_register_prepopulates_ring():
    arena = BufferArena(ring=2)
    arena.register("p", "host", (128, 4), np.float32)
    assert arena.stats.entries == 2
    arena.acquire("p", "host", (128, 4), np.float32)
    s = arena.stats
    assert s.hits == 1 and s.misses == 0


def test_evict_drops_only_that_program():
    arena = BufferArena(ring=2)
    arena.register("keep", "host", (64,), np.float32)
    arena.register("drop", "host", (64,), np.float32)
    assert arena.evict("drop") == 2
    s = arena.stats
    assert s.entries == 2  # keep's ring intact
    assert arena.evict("keep") == 2
    assert arena.stats.entries == 0


def test_close_refuses_further_acquires():
    arena = BufferArena()
    lease = arena.acquire("p", "host", (4,), np.float32)
    arena.close()
    assert arena.stats.entries == 0
    lease.array[:] = 1.0  # holder's view stays valid
    with pytest.raises(RuntimeError, match="closed"):
        arena.acquire("p", "host", (4,), np.float32)


def test_capacity_bounds_free_pool_lru():
    arena = BufferArena(capacity_bytes=4096, ring=4)
    leases = [
        arena.acquire("p", "host", (2048,), np.uint8) for _ in range(4)
    ]
    for lease in leases:
        arena.release(lease)  # 4 x 2048B free > 4096B capacity
    s = arena.stats
    assert s.bytes_pooled <= 4096
    assert s.evictions >= 2


@settings(max_examples=30, deadline=None)
@given(
    st.lists(
        st.tuples(
            st.integers(min_value=0, max_value=2),  # program index
            st.integers(min_value=0, max_value=3),  # shape index
            st.integers(min_value=0, max_value=2),  # 0/1 acquire, 2 release
        ),
        min_size=1,
        max_size=60,
    )
)
def test_arena_invariants_under_submit_sequences(ops):
    """LRU eviction bounds: whatever the submit sequence, the free pool
    never exceeds capacity, per-key entries never exceed the ring, and
    every lease stays usable."""
    capacity = 1 << 14
    ring = 2
    arena = BufferArena(capacity_bytes=capacity, ring=ring)
    shapes = [(256,), (1024,), (333,), (2048,)]
    held = []
    for prog_i, shape_i, kind in ops:
        if kind == 2 and held:
            arena.release(held.pop(0))
        else:
            lease = arena.acquire(
                f"prog{prog_i}", "host", shapes[shape_i], np.float32
            )
            lease.array.fill(prog_i)  # the view must be writable
            held.append(lease)
        s = arena.stats
        assert s.bytes_pooled <= capacity          # LRU bound on free pool
        assert s.leases_out <= s.entries
        assert s.bytes_total == s.bytes_pooled + s.bytes_leased
        assert s.acquires == s.hits + s.rekeys + s.misses + s.recycles
    # tracked entries per key never exceed the ring
    for ents in arena._by_key.values():
        assert len(ents) <= ring


# --------------------------------------------------------------- pipeline


def test_pipeline_prefetch_and_staged_commits():
    pool = WorkerPool(name="pipe-test")
    pipe = TransferPipeline(pool, async_threshold_bytes=1024)
    pipe.start()
    fut = pipe.prefetch(lambda: 41 + 1)
    assert fut.result() == 42
    out = np.zeros(8, np.int64)

    def commit_small():
        out[0] = 1

    def commit_large():
        out[1] = 2

    pipe.stage_out(commit_small, nbytes=64)  # below threshold: inline
    assert out[0] == 1
    pipe.stage_out(commit_large, nbytes=4096)  # above: committer thread
    pipe.flush()
    assert out[1] == 2
    assert pipe.commits == 2
    pipe.close()
    pool.close()


def test_pipeline_prefetch_error_surfaces_at_result():
    pool = WorkerPool(name="pipe-err")
    pipe = TransferPipeline(pool)
    pipe.start()

    def boom():
        raise ValueError("staging failed")

    fut = pipe.prefetch(boom)
    with pytest.raises(ValueError, match="staging failed"):
        fut.result()
    pipe.close()
    pool.close()


# ------------------------------------------------------------ pooled runs


def test_pooled_bit_identical_outputs_all_schedulers():
    """Integer mandelbrot: pooled and per-packet outputs must be
    bit-identical under every registered scheduler (and match the
    single-device oracle)."""
    ref = P.reference_output("mandelbrot2d", **MANDEL_KW)
    for name in available_schedulers():
        outs = {}
        for policy in (BufferPolicy.POOLED, BufferPolicy.PER_PACKET):
            prog = P.PROGRAMS["mandelbrot2d"](**MANDEL_KW)
            res = coexec(
                prog, devices3(), scheduler=name, buffer_policy=policy
            )
            outs[policy] = np.array(res.output, copy=True)
        np.testing.assert_array_equal(
            outs[BufferPolicy.POOLED], outs[BufferPolicy.PER_PACKET],
            err_msg=f"scheduler {name}",
        )
        np.testing.assert_array_equal(
            outs[BufferPolicy.POOLED], ref, err_msg=f"scheduler {name}"
        )


def test_pooled_float_outputs_match_reference_all_schedulers():
    ref = P.reference_output("gaussian2d", **GAUSS_KW)
    for name in available_schedulers():
        prog = P.PROGRAMS["gaussian2d"](**GAUSS_KW)
        res = coexec(
            prog, devices3(), scheduler=name,
            buffer_policy=BufferPolicy.POOLED,
        )
        np.testing.assert_allclose(
            res.output, ref, rtol=1e-5, atol=1e-5, err_msg=f"scheduler {name}"
        )


def test_roi_submits_default_to_pooled_and_recycle_the_ring():
    prog = P.PROGRAMS["gaussian2d"](**GAUSS_KW)
    with EngineSession(devices3()) as session:
        session.register_workload(prog)
        assert session.arena_stats.entries == 2  # ring pre-registered
        r1 = session.submit(prog, mode=OffloadMode.ROI).result()
        r2 = session.submit(prog, mode=OffloadMode.ROI).result()
        r3 = session.submit(prog, mode=OffloadMode.ROI).result()
        # double-buffer contract: the ring cycles every `ring` submits
        assert not np.shares_memory(r1.output, r2.output)
        assert np.shares_memory(r1.output, r3.output)
        s = session.arena_stats
        assert s.acquires == 3 and s.misses == 0
        # an explicit REGISTERED submit must not touch the arena
        session.submit(
            prog, mode=OffloadMode.ROI,
            buffer_policy=BufferPolicy.REGISTERED,
        ).result()
        assert session.arena_stats.acquires == 3


def test_unregister_workload_evicts_arena_entries():
    prog = P.PROGRAMS["gaussian2d"](**GAUSS_KW)
    with EngineSession(devices3()) as session:
        session.register_workload(prog)
        session.submit(prog, mode=OffloadMode.ROI).result()
        assert session.arena_stats.entries > 0
        session.unregister_workload(prog.name)
        assert session.arena_stats.entries == 0


def test_phase_identity_all_policies():
    """The five phase windows are disjoint wall segments:
    init + h2d + roi + d2h + teardown == wall, exactly."""
    for policy in (
        BufferPolicy.POOLED,
        BufferPolicy.REGISTERED,
        BufferPolicy.PER_PACKET,
    ):
        prog = P.PROGRAMS["gaussian2d"](**GAUSS_KW)
        res = coexec(prog, devices3(), buffer_policy=policy)
        ph = res.phases
        wall = ph.init_s + ph.h2d_s + ph.roi_s + ph.d2h_s + ph.teardown_s
        assert wall == pytest.approx(res.binary_time, rel=1e-6), policy
        assert ph.offload_s == pytest.approx(
            ph.h2d_s + ph.roi_s + ph.d2h_s, rel=1e-6
        ), policy
        assert ph.roi_s == res.total_time
        assert ph.binary == pytest.approx(res.binary_time, rel=1e-6)


def test_pooled_fault_tolerance_requeues_and_stays_exact():
    """A device dying mid-run under the pipelined loop: its packet is
    requeued and the survivors produce the exact output."""
    ref = P.reference_output("mandelbrot2d", **MANDEL_KW)
    devs = [
        DeviceGroup("flaky", throttle=1.5, fail_after=0),
        DeviceGroup("igpu", throttle=2.0),
        DeviceGroup("gpu", throttle=1.0),
    ]
    prog = P.PROGRAMS["mandelbrot2d"](**MANDEL_KW)
    res = coexec(
        prog, devs, scheduler="dynamic",
        scheduler_kwargs={"n_packets": 6},
        buffer_policy=BufferPolicy.POOLED,
    )
    assert res.aborted_devices == 1
    assert res.retries >= 1
    np.testing.assert_array_equal(res.output, ref)


def test_pooled_stage_in_failure_releases_device(monkeypatch):
    """A stage-in (launch-bind) failure under the pipelined loop must mark
    the device dead and release its pre-assigned chunk — survivors absorb
    the work instead of livelocking on a stranded static chunk."""
    from repro.core import runtime as R

    orig = R._RunContext._invoke
    tripped = {"n": 0}

    def flaky_invoke(self, fn, region):
        if tripped["n"] == 0:
            tripped["n"] += 1
            raise ValueError("bad geometry")
        return orig(self, fn, region)

    monkeypatch.setattr(R._RunContext, "_invoke", flaky_invoke)
    ref = P.reference_output("mandelbrot2d", **MANDEL_KW)
    prog = P.PROGRAMS["mandelbrot2d"](**MANDEL_KW)
    res = coexec(
        prog, devices3(), scheduler="static",
        buffer_policy=BufferPolicy.POOLED,
    )
    assert tripped["n"] == 1
    assert res.aborted_devices == 1
    np.testing.assert_array_equal(res.output, ref)


# -------------------------------------------------------------- simulator


def test_simulator_pooled_overlap_ordering_and_phases():
    dev = [SimDevice("gpu", 1000.0, transfer_in=2e-4, transfer_out=2e-4)]
    times = {}
    for policy in ("per_packet", "registered", "pooled"):
        r = simulate(
            4096, 8, dev,
            SimConfig(
                scheduler="dynamic",
                scheduler_kwargs={"n_packets": 16},
                buffer_policy=policy,
            ),
        )
        times[policy] = r.total_time
        assert r.phases.roi_s == r.total_time
        if policy == "pooled":
            # only the pipeline fill is unhidden
            assert r.phases.h2d_s < times_reg_h2d
            assert r.phases.d2h_s <= times_reg_d2h
        elif policy == "registered":
            times_reg_h2d = r.phases.h2d_s
            times_reg_d2h = r.phases.d2h_s
            assert r.phases.h2d_s > 0 and r.phases.d2h_s > 0
    assert times["pooled"] < times["registered"] < times["per_packet"]


def test_simconfig_policy_resolution_backcompat():
    assert SimConfig().policy == "per_packet"
    assert SimConfig(opt_buffers=True).policy == "registered"
    cfg = SimConfig(opt_buffers=True, buffer_policy="pooled")
    assert cfg.policy == "pooled"


# ------------------------------------------------- close-ordering bugfix


def test_close_drains_inflight_pooled_submits_without_leaking_arena():
    """Regression: close() must drain the dispatch queue and release the
    arena BEFORE WorkerPool.close() — a close racing in-flight pooled
    submits must not leak arena entries (or wedge on a dead pool)."""
    prog = P.PROGRAMS["gaussian2d"](**GAUSS_KW)
    ref = P.reference_output("gaussian2d", **GAUSS_KW)
    session = EngineSession(devices3())
    session.register_workload(prog)
    handles = [
        session.submit(prog, mode=OffloadMode.ROI) for _ in range(5)
    ]
    session.close()  # races the queued submits: drain, then release
    for h in handles:
        res = h.result(timeout=60)  # every queued run completed
        np.testing.assert_allclose(res.output, ref, rtol=1e-5, atol=1e-5)
    s = session.arena_stats
    assert s.entries == 0 and s.bytes_total == 0
    with pytest.raises(RuntimeError, match="closed"):
        session.submit(prog, mode=OffloadMode.ROI)


def test_close_drains_pending_graph_submits_without_leaking_arena():
    """Graph variant of the close race: close() arriving while DEPENDENT
    pooled submits are still pending must drain the graph topologically
    (dependents run after their predecessors, before the arena/pool shut
    down) — no leaked _Submissions, no leaked arena entries."""
    prog = P.PROGRAMS["gaussian2d"](**GAUSS_KW)
    ref = P.reference_output("gaussian2d", **GAUSS_KW)
    session = EngineSession(devices3(), max_inflight=2)
    session.register_workload(prog)
    seen = []
    root = session.submit(prog, mode=OffloadMode.ROI)
    mids = [
        session.submit(
            prog,
            mode=OffloadMode.ROI,
            deps=[root],
            feed=lambda results: seen.append(len(results)),
        )
        for _ in range(3)
    ]
    leaf = session.submit(prog, mode=OffloadMode.ROI, deps=mids)
    session.close()  # must drain root -> mids -> leaf, then release
    for h in [root, *mids, leaf]:
        res = h.result(timeout=60)
        np.testing.assert_allclose(res.output, ref, rtol=1e-5, atol=1e-5)
    assert seen == [1, 1, 1]  # every mid's feed saw its predecessor
    assert len(session._pending) == 0 and session._inflight == 0
    s = session.arena_stats
    assert s.entries == 0 and s.bytes_total == 0


def test_close_is_idempotent_and_arena_closed():
    session = EngineSession(devices3())
    session.close()
    session.close()
    with pytest.raises(RuntimeError, match="closed"):
        session.arena.acquire("p", "host", (4,), np.float32)


# --------------------------------------------------------- output free list


def test_output_free_list_hands_a_dropped_buffer_out_again():
    outs = OutputFreeList()
    a, reused = outs.acquire((4, 8), np.float32)
    assert not reused
    where = a.ctypes.data
    del a
    b, reused = outs.acquire((4, 8), np.float32)
    assert reused and b.ctypes.data == where
    # another shape or dtype never shares it
    c, reused_c = outs.acquire((4, 8), np.int32)
    d, reused_d = outs.acquire((8, 4), np.float32)
    assert not reused_c and not reused_d
    s = outs.stats
    assert (s.hits, s.misses) == (1, 3)
    assert s.idle == 0 and s.bytes_held == 0


class _Held:
    """Stands for a RunResult: keeps the output as an attribute."""

    def __init__(self, output):
        self.output = output


HOLDERS = {
    "result": _Held,
    "array": lambda a: a,
    "row_slice": lambda a: a[1:3],
    "reshaped": lambda a: a.reshape(-1),
    "asarray_view": lambda a: np.asarray(a)[:, ::2],
}


@pytest.mark.parametrize("holder", sorted(HOLDERS))
def test_output_free_list_never_hands_out_a_buffer_still_referred_to(holder):
    outs = OutputFreeList()
    a, _ = outs.acquire((4, 8), np.float32)
    a[...] = 7.0
    kept = HOLDERS[holder](a)
    del a
    b, reused = outs.acquire((4, 8), np.float32)
    held = kept.output if isinstance(kept, _Held) else kept
    assert not reused and not np.shares_memory(b, held)
    b[...] = -1.0
    assert np.all(held == 7.0)                   # never aliased
    del kept, held
    c, reused = outs.acquire((4, 8), np.float32)  # the first, now dropped
    assert reused and not np.shares_memory(b, c)


def test_output_free_list_concurrent_acquires_get_distinct_buffers():
    outs = OutputFreeList()
    for _ in range(2):                          # two idle buffers to race for
        outs.acquire((64, 64), np.float32)
    n = 16
    got = [None] * n
    start = threading.Barrier(n)

    def take(i):
        start.wait(timeout=10)
        got[i] = outs.acquire((64, 64), np.float32)[0]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=take, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert len({b.ctypes.data for b in got}) == n
    s = outs.stats
    assert (s.hits, s.misses) == (2, n)


def test_output_free_list_keeps_at_most_two_idle_per_shape():
    outs = OutputFreeList()
    held = [outs.acquire((16, 16), np.float32)[0] for _ in range(5)]
    other = outs.acquire((16, 16), np.float64)[0]
    del held, other
    s = outs.stats
    assert s.idle == OutputFreeList.KEEP + 1
    assert s.bytes_held == 2 * 16 * 16 * 4 + 16 * 16 * 8


def test_output_free_list_close_releases_every_buffer():
    session = EngineSession(devices3())
    prog = P.PROGRAMS["gaussian"](h=256, w=128)
    res = session.submit(prog, mode=OffloadMode.BINARY).result()
    del res
    # the session's runner thread lets go of the result a moment after
    # result() returned
    t_end = time.monotonic() + 10
    while session.output_stats.idle == 0 and time.monotonic() < t_end:
        time.sleep(1e-3)
    s = session.output_stats
    assert (s.misses, s.idle) == (1, 1)
    assert s.bytes_held == 256 * 128 * 4
    session.close()
    s = session.output_stats
    assert s.idle == 0 and s.bytes_held == 0
