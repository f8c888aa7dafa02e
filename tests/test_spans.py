"""The runtime's own profiler spans and counters: a profiler trace of a
tiny two-group ``EngineSession`` holds one ``coexec.*`` span per runtime
step, on the thread that did the work; ``RunResult`` carries the queue
wait, the commit time, the jit lowerings of each packet shape, and when
each device finished its last packet."""
import collections
import glob
import os
import tempfile
import time

import pytest

import jax

from repro.api import EngineSession, OffloadMode
from repro.core import programs as P
from repro.core.device import DeviceGroup
from repro.core.metrics import balance
from repro.core.region import Region

# an image width no other test compiles, so that its packet shapes are new
WIDTH = 136


def _events(log_dir):
    """(name, start_ns, end_ns, thread line, stats) of every ``coexec.*``
    event in the trace under ``log_dir``."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("coexec."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                (plane.name, k), dict(e.stats)))
    return out


def _traced(run):
    """``run(session)`` on a fresh two-group session, under a profiler
    trace; returns (its result, the trace's coexec events).  The session
    closes before the trace stops: pool threads must not outlive a
    trace they wrote into."""
    groups = [DeviceGroup("fast"), DeviceGroup("slow", throttle=2.0)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with EngineSession(groups, async_threshold_bytes=0) as s:
                result = run(s)
        finally:
            jax.profiler.stop_trace()
        return result, groups, _events(d)


def _timed(session, prog, **kw):
    """(result, wall seconds) of one submit."""
    t0 = time.perf_counter()
    result = session.submit(prog, **kw).result()
    return result, time.perf_counter() - t0


def _check_packets(result, groups, events):
    named = collections.defaultdict(list)
    for ev in events:
        named[ev[0]].append(ev)
    want = collections.Counter((groups[p.device].name, p.size)
                               for p in result.packets)
    for name in ("coexec.packet", "coexec.commit"):
        got = collections.Counter((st["group"], st["size"])
                                  for *_, st in named[name])
        assert got == want, name
    assert sorted(st["group"] for *_, st in named["coexec.build"]) == \
        ["fast", "slow"]
    packets = named["coexec.packet"]
    for name in ("coexec.launch", "coexec.wait"):
        assert len(named[name]) == len(packets)
        for _, s, e, line, _ in named[name]:
            assert any(ps <= s and e <= pe and pl == line
                       for _, ps, pe, pl, _ in packets), name
    for name in ("coexec.init", "coexec.drain", "coexec.d2h"):
        assert len(named[name]) == 1, name
    # the run's commit time is its commit spans' summed length
    spans_s = sum(e - s for _, s, e, _, _ in named["coexec.commit"]) * 1e-9
    assert 0 < result.commit_s <= spans_s * 1.01 + 1e-6
    assert result.commit_s == pytest.approx(spans_s, rel=0.1, abs=1e-4)
    return named


def test_binary_submit_spans_on_the_sync_path():
    prog = P.PROGRAMS["gaussian"](h=1024, w=WIDTH, seed=3)
    (result, wall), groups, events = _traced(
        lambda s: _timed(s, prog, mode=OffloadMode.BINARY))
    named = _check_packets(result, groups, events)
    # the run's teardown and the session's BINARY eviction
    assert len(named["coexec.teardown"]) == 2
    assert 0 <= result.queue_s <= wall
    # the sync loop commits on the device thread that ran the packet
    lines = {st["group"]: line for _, _, _, line, st in
             named["coexec.packet"]}
    for _, _, _, line, st in named["coexec.commit"]:
        assert line == lines[st["group"]]


def test_roi_submit_spans_on_the_pooled_path():
    prog = P.PROGRAMS["gaussian2d"](h=256, w=256, seed=4)

    def run(s):
        s.register_workload(prog)
        return _timed(s, prog, mode=OffloadMode.ROI,
                      region=Region.rect(128, 192, lws=(32, 32),
                                         offset=(64, 32)))
    (result, wall), groups, events = _traced(run)
    named = _check_packets(result, groups, events)
    assert len(named["coexec.teardown"]) == 1
    assert 0 <= result.queue_s <= wall
    # every commit went to the committer thread
    device_lines = {line for *_, line, _ in named["coexec.packet"]}
    assert not device_lines & {line for *_, line, _ in
                               named["coexec.commit"]}


def test_compiles_count_a_new_packet_size_once():
    prog = P.PROGRAMS["gaussian"](h=1024, w=WIDTH + 8, seed=5)
    with EngineSession([DeviceGroup("solo")]) as s:
        first = s.submit(prog, mode=OffloadMode.BINARY).result()
        again = s.submit(prog, mode=OffloadMode.BINARY).result()
    sizes = {p.size for p in first.packets}
    assert first.compiles == {("solo", n): 1 for n in sizes}
    assert again.compiles == {}
    assert {p.size for p in again.packets} == sizes


def test_device_finish_is_the_end_of_each_devices_last_packet():
    """Half the work each on a fast and a 4x throttled group: the fast
    group finishes first, so the split reads unbalanced."""
    prog = P.PROGRAMS["gaussian"](h=1024, w=128, seed=6)
    groups = [DeviceGroup("fast"), DeviceGroup("slow", throttle=4.0)]
    with EngineSession(groups) as s:
        r = s.submit(prog, scheduler="static", powers=[1.0, 1.0]).result()
    fast, slow = r.device_finish
    assert 0 < fast < slow <= r.total_time
    assert balance(r) < 1.0
    assert balance(r) == pytest.approx(fast / slow)
