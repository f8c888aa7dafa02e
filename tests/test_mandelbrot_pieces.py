"""Mandelbrot's range entry runs a packet as power-of-two row pieces, so
a device compiles a bounded set of kernel shapes whatever sizes HGuided
carves; the runtime commits such a packet piece by piece, and times its
commits per device.  All on the CPU's jnp path."""
import collections
import glob
import math
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.api import BufferPolicy, EngineSession, OffloadMode
from repro.ckpt.checkpoint import RunJournal
from repro.core import programs as P
from repro.core.device import DeviceGroup
from repro.core.runtime import RowPieces, write_rows
from repro.kernels.mandelbrot import ops, ref as R

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def _groups(n=4):
    return [DeviceGroup(f"g{i}") for i in range(n)]


def _image(px, max_iter):
    return np.asarray(R.escape_counts(0, px, px, px, max_iter))


@pytest.mark.parametrize("size", [1, 2, 7, 8, 448, 897, 1792])
def test_pieces_are_the_binary_digits_of_the_size(size):
    got = ops.pieces(size)
    lengths = [n for _, n in got]
    assert sum(lengths) == size
    assert lengths == sorted(set(lengths), reverse=True)
    assert all(n & (n - 1) == 0 for n in lengths)
    assert [s for s, _ in got] == list(np.cumsum([0] + lengths[:-1]))


@pytest.mark.parametrize("n_wg", [19, 1792])
def test_every_size_up_to_n_uses_at_most_log2_lengths(n_wg):
    """Table I's 1792 work-groups: 11 piece lengths, under the bound of
    ceil(log2 1792) + 1 = 12, whatever sizes a scheduler carves."""
    lengths = {n for size in range(1, n_wg + 1)
               for _, n in ops.pieces(size)}
    assert len(lengths) <= math.ceil(math.log2(n_wg)) + 1


def test_range_entry_equals_the_reference_tile_for_every_size():
    px, max_iter = 80, 30
    n_wg = ops.total_work(px)
    want = _image(px, max_iter)
    fn = P.mandelbrot_program(px=px, max_iter=max_iter).build(
        DeviceGroup("g0"))
    for size in range(1, n_wg + 1):
        for offset in sorted({0, (n_wg - size) // 2, n_wg - size}):
            got = fn(offset, size)
            assert isinstance(got, RowPieces)
            assert [p.shape[0] for p in got] == [
                n * ops.LWS for _, n in ops.pieces(size)]
            rows = np.concatenate([np.asarray(p) for p in got])
            r0 = offset * ops.LWS
            np.testing.assert_array_equal(
                rows, want[r0:r0 + size * ops.LWS], err_msg=f"{offset=}")


def test_range_entry_compiles_at_most_log2_shapes_per_device():
    """Every packet size of a fresh image lowers at most
    ceil(log2 n_wg) + 1 tile shapes."""
    px, max_iter = 152, 13           # 19 work-groups, no other test's
    n_wg = ops.total_work(px)
    lowerings = []

    def listen(event, duration, **_):
        if event == LOWERING:
            lowerings.append(event)

    fn = P.mandelbrot_program(px=px, max_iter=max_iter).build(
        DeviceGroup("g0"))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for size in range(1, n_wg + 1):
            jax.block_until_ready(fn(0, size))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert 0 < len(lowerings) <= math.ceil(math.log2(n_wg)) + 1


@pytest.mark.parametrize("policy", list(BufferPolicy))
def test_four_groups_compute_the_reference_exactly(policy):
    """HGuided over four groups, BINARY: the output equals the reference
    and every work-group ran exactly once, in the sync loop (registered,
    per-packet copies) and the pipelined one (pooled)."""
    px, max_iter = 256, 60
    with EngineSession(_groups(), buffer_policy=policy) as session:
        assert session.scheduler == "hguided_opt"
        res = session.submit(P.mandelbrot_program(px=px, max_iter=max_iter),
                             mode=OffloadMode.BINARY).result()
        out = np.asarray(res.output).reshape(px, px).copy()
    np.testing.assert_array_equal(out, _image(px, max_iter))
    covered = np.zeros(ops.total_work(px), np.int64)
    for p in res.packets:
        covered[p.offset:p.offset + p.size] += 1
    assert (covered == 1).all()


def _commit_spans_s(log_dir):
    """{group: summed seconds of its ``coexec.commit`` spans} in the
    profiler trace under ``log_dir``."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = collections.defaultdict(float)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "coexec.commit":
                    out[dict(e.stats)["group"]] += (e.end_ns - e.start_ns) * 1e-9
    return out


@pytest.mark.parametrize("policy", [BufferPolicy.REGISTERED,
                                    BufferPolicy.POOLED])
def test_device_commit_s_sums_to_commit_s(policy):
    """Each device's commit seconds are its own group's ``coexec.commit``
    spans, in the sync loop (registered) and the pipelined one (pooled,
    every commit on the committer thread); they sum to ``commit_s``."""
    groups = _groups()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with EngineSession(groups, buffer_policy=policy,
                               async_threshold_bytes=0) as session:
                res = session.submit(
                    P.mandelbrot_program(px=256, max_iter=20),
                    mode=OffloadMode.BINARY).result()
        finally:
            jax.profiler.stop_trace()
        spans_s = _commit_spans_s(d)
    assert len(res.device_commit_s) == 4
    assert sum(res.device_commit_s) == res.commit_s > 0
    ran = {p.device for p in res.packets}
    for i, (g, t) in enumerate(zip(groups, res.device_commit_s)):
        assert (t > 0) == (i in ran), (i, res.device_commit_s)
        assert t <= spans_s[g.name] * 1.01 + 1e-6, (g.name, t, spans_s)
        assert t == pytest.approx(spans_s[g.name], rel=0.1, abs=1e-4)


def test_journal_records_the_rows_of_a_packet_of_pieces():
    px, max_iter = 128, 25
    prog = P.mandelbrot_program(px=px, max_iter=max_iter)
    path = os.path.join(tempfile.mkdtemp(prefix="pieces-"), "run.journal")
    with EngineSession(_groups()) as session, RunJournal(path) as journal:
        session.submit(prog, journal=journal).result()
    want = _image(px, max_iter).reshape(-1, 1)
    records = RunJournal.read(path)["mandelbrot"]
    assert sum(r.size for r in records) == ops.total_work(px)
    rows = ops.LWS * px
    for r in records:
        np.testing.assert_array_equal(
            r.data, want[r.offset * rows:(r.offset + r.size) * rows])


def test_collect_hook_is_handed_the_pieces():
    px, max_iter = 128, 25
    got = np.zeros((px, px), np.int32)

    def collect(pkt, res, dev):
        assert isinstance(res, RowPieces)
        write_rows(res, got[pkt.offset * ops.LWS:
                            (pkt.offset + pkt.size) * ops.LWS].reshape(-1, 1))

    with EngineSession(_groups()) as session:
        session.submit(P.mandelbrot_program(px=px, max_iter=max_iter),
                       collect=collect).result()
    np.testing.assert_array_equal(got, _image(px, max_iter))


def test_write_rows_refuses_pieces_that_do_not_fill_the_packet():
    rows = np.zeros((6, 2), np.int32)
    got = RowPieces([np.ones((4, 2)), np.full((2, 2), 2)])
    assert write_rows(got, rows) == 6 * 2 * 8
    np.testing.assert_array_equal(rows[:, 0], [1, 1, 1, 1, 2, 2])
    for bad in ((np.ones((4, 2)),), (np.ones((4, 2)), np.ones((4, 2))),
                (np.ones(11), np.ones(1))):
        with pytest.raises(ValueError):
            write_rows(RowPieces(bad), np.zeros((6, 2), np.int32))


def test_row_pieces_add_a_scalar_to_every_piece():
    got = RowPieces([np.zeros((2, 3), np.int32), np.ones((1, 3), np.int32)])
    bumped = got + 1
    assert isinstance(bumped, RowPieces) and len(bumped) == 2
    rows = np.zeros((3, 3), np.int32)
    write_rows(bumped, rows)
    np.testing.assert_array_equal(rows[:, 0], [1, 1, 2])
    assert len(jax.tree.leaves(bumped)) == 2


@pytest.mark.parametrize("policy", [BufferPolicy.REGISTERED,
                                    BufferPolicy.POOLED])
def test_a_short_piece_fails_the_run(policy):
    """A range function whose pieces leave rows of the packet unwritten
    fails the run instead of committing stale rows."""
    prog = P.mandelbrot_program(px=64, max_iter=10)
    build = prog.build

    def short(group):
        fn = build(group)

        def run(offset, size):
            *head, last = fn(offset, size)
            return RowPieces([*head, last[:-1]])
        return run
    prog.build = short
    with EngineSession(_groups(2), buffer_policy=policy) as session:
        with pytest.raises(RuntimeError):
            session.submit(prog).result()
