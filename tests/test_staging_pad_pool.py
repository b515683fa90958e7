"""The pad pool of the staging layer (`ml/_staging.py` `_PadPool`): a padded
host copy is written into a retained buffer whose pages are warm. On this
backend `device_put` may alias the host, so the public functions bypass the
pool (`_aliases_host`); the pool itself is driven here directly and through
the pad step with the bypass switched off."""

import threading

import numpy as np
import pytest

from sml_tpu.ml import _staging
from sml_tpu.ml._staging import RowsLast, _PadPool
from sml_tpu.parallel import mesh as meshlib

MB = 1 << 20


class Placed:
    """What the pool keeps of a placed array: whether it is ready, and a
    `block_until_ready` that says it was waited for."""

    def __init__(self, ready):
        self.ready = ready
        self.waited = 0

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.waited += 1
        self.ready = True
        return self


@pytest.fixture()
def pool(monkeypatch):
    """A pool of its own under the module's name, and the bypass off: what
    a mesh of devices with memory of their own gets."""
    mine = _PadPool(64 * MB)
    monkeypatch.setattr(_staging, "_PAD_POOL", mine)
    monkeypatch.setattr(_staging, "_aliases_host", lambda mesh: False)
    return mine


def _block(rng, rows, width, dtype, rows_last):
    shape = (width, rows) if rows_last else (rows, width)
    if np.dtype(dtype).kind == "f":
        return rng.normal(size=shape).astype(dtype)
    return rng.integers(0, 200, size=shape).astype(dtype)


def _np_pad(a, rows, axis):
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, (-a.shape[axis]) % rows)
    return np.pad(a, widths)


@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.uint8])
@pytest.mark.parametrize("axis", [0, -1])
def test_the_pooled_pad_is_np_pad_to_the_byte(pool, axis, dtype):
    """Fresh, then warm; and the SHORTER split after the longer one of the
    same bucket has a zero tail where the longer one's rows lay."""
    rng = np.random.default_rng(44)
    rows = meshlib.bucket_rows(600_000, 8)
    seen = set()
    for n, warm in ((600_000, False), (610_011, True), (590_001, True)):
        a = _block(rng, n, 5, dtype, axis == -1)
        padded, buf = _staging._padded_rows(a, rows, None, axis)
        want = _np_pad(a, rows, axis)
        assert padded.dtype == want.dtype and padded.shape == want.shape
        assert padded.tobytes() == want.tobytes()
        assert buf is not None and padded.base is buf
        assert pool.stats()["buffers"] == 0      # taken: not the pool's
        seen.add(buf.ctypes.data)
        pool.give_back(buf)
        assert pool.stats() == {"buffers": 1, "bytes": want.nbytes}
    assert len(seen) == 1       # one buffer, written three times


@pytest.mark.parametrize("axis", [0, -1])
def test_the_notes_and_counters_say_warm_or_fresh(pool, axis):
    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        rng = np.random.default_rng(7)
        for n in (300_000, 300_007):
            a = _block(rng, n, 2, np.float32, axis == -1)
            _, buf = _staging._padded_rows(a, meshlib.bucket_rows(n, 8),
                                           None, axis)
            pool.give_back(buf)
        pads = [e for e in obs.RECORDER.events() if e.name == "stage.pad"]
        assert [e.args["warm"] for e in pads] == [False, True]
        assert pads[0].args["bytes"] == pads[1].args["bytes"] == buf.nbytes
        counters = obs.RECORDER.counters()
        assert counters["staging.pad_fresh"] == 1.0
        assert counters["staging.pad_warm"] == 1.0
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()


@pytest.mark.parametrize("rows_last", [False, True])
def test_two_arrays_of_one_padded_shape_keep_their_own_rows(rows_last):
    """The aliasing trap: on this backend a placed array may BE the host
    buffer, so through the public function (no fixture: the bypass as it
    is) the second staging must not write over the first's cached array."""
    rng = np.random.default_rng(45)
    assert _staging._aliases_host(meshlib.get_mesh())
    _staging._PAD_POOL.clear()
    arrays = [_block(rng, n, 3, np.float32, rows_last)
              for n in (400_003, 400_001)]
    staged = [_staging.stage_rows_cached(RowsLast(a) if rows_last else a)
              for a in arrays]
    axis = -1 if rows_last else 0
    rows = staged[0].shape[axis]
    assert staged[1].shape == staged[0].shape
    for a, dev in zip(arrays, staged):
        np.testing.assert_array_equal(np.asarray(dev), _np_pad(a, rows, axis))
    assert _staging._PAD_POOL.stats()["buffers"] == 0


def test_with_the_pool_on_a_transfer_is_waited_out_before_the_next_pad(pool):
    """Three stagings of one padded shape with the pool ON, as on a chip,
    out of ONE buffer: `device_put` returns before the host memory has been
    read, the pool waits for the placed array before the next pad, and all
    three cached arrays hold their own rows. The buffer lies 16 bytes off
    the 64 this backend wants before it takes host memory without a copy,
    so every shard is a transfer."""
    rng = np.random.default_rng(46)
    arrays = [_block(rng, n, 3, np.float32, False)
              for n in (400_007, 400_005, 400_002)]
    rows = meshlib.bucket_rows(400_007, 8)
    raw = np.empty(rows * 12 + 64, np.uint8)
    skew = (16 - raw.ctypes.data) % 64
    pool.give_back(raw[skew:skew + rows * 12])
    staged = []
    for a in arrays:
        staged.append(_staging.stage_rows_cached(a))
        assert pool.stats()["buffers"] == 1      # given back with the put
        lo = staged[-1].addressable_shards[0].data.unsafe_buffer_pointer()
        assert not 0 <= lo - raw.ctypes.data < raw.nbytes
    for a, dev in zip(arrays, staged):
        np.testing.assert_array_equal(np.asarray(dev), _np_pad(a, rows, 0))


def test_a_buffer_being_read_is_not_handed_out():
    """Room for two: the second pad of a size gets a buffer of its own
    while the first's transfer runs. No room: the pool waits the transfer
    out (`block_until_ready`) and only then hands the buffer out."""
    roomy, tight = _PadPool(8 * MB), _PadPool(3 * MB)
    for pool in (roomy, tight):
        first, warm = pool.take(2 * MB)
        assert not warm and first.nbytes == 2 * MB
        reading = Placed(ready=False)
        pool.give_back(first, reading)
        second, warm = pool.take(2 * MB)
        if pool is roomy:
            assert second is not first and not warm and reading.waited == 0
            pool.give_back(second, Placed(ready=True))
            assert pool.stats()["buffers"] == 2
            reading.ready = True        # by the next fit both are free
            again, warm = pool.take(2 * MB)
            assert again is first and warm and reading.waited == 1
        else:
            assert second is first and warm and reading.waited == 1
    # a placed array that is gone holds nothing back
    pool = _PadPool(8 * MB)
    buf, _ = pool.take(MB)
    pool.give_back(buf, Placed(ready=False))     # dropped at once
    got, warm = pool.take(MB)
    assert got is buf and warm


def test_a_ready_buffer_is_preferred_to_one_being_read():
    pool = _PadPool(8 * MB)
    a, b = pool.take(MB)[0], pool.take(MB)[0]
    busy, done = Placed(ready=False), Placed(ready=True)
    pool.give_back(a, busy)
    pool.give_back(b, done)
    got, warm = pool.take(MB)
    assert got is b and warm and busy.waited == 0


@pytest.mark.parametrize("site", ["rows", "rows_last", "bins", "aligned",
                                  "mask"])
def test_an_array_under_span_bytes_never_enters_the_pool(pool, site):
    rng = np.random.default_rng(47)
    mesh = meshlib.get_mesh()
    n, rows = 20_000, meshlib.bucket_rows(20_000, 8)
    if site == "mask":
        out, buf = _staging._zero_tailed((rows,), np.float32, 0, 1.0, n,
                                         4 * rows, mesh)
        np.testing.assert_array_equal(out, meshlib.row_mask(rows, n))
    elif site == "aligned":
        y = rng.normal(size=n)
        out, buf = _staging._padded_rows(y, rows, mesh, dtype=np.float32)
        np.testing.assert_array_equal(
            out, _np_pad(y.astype(np.float32), rows, 0))
    else:
        dtype = np.uint8 if site == "bins" else np.float32
        axis = -1 if site == "rows_last" else 0
        a = _block(rng, n, 4, dtype, axis == -1)
        out, buf = _staging._padded_rows(a, rows, mesh, axis)
        assert out.tobytes() == _np_pad(a, rows, axis).tobytes()
    assert buf is None and out.base is None
    assert pool.stats() == {"buffers": 0, "bytes": 0}


@pytest.mark.parametrize("site", ["aligned", "mask"])
def test_the_other_two_sites_draw_from_the_same_pool(pool, site):
    """`stage_aligned`'s float32 copy and the mask's fill: the same step
    into the same buffers (2 MB each here)."""
    rng = np.random.default_rng(48)
    rows = meshlib.bucket_rows(500_000, 8)
    for n, warm in ((500_000, False), (480_001, True)):
        if site == "mask":
            out, buf = _staging._zero_tailed((rows,), np.float32, 0, 1.0, n,
                                             4 * rows, None)
            want = meshlib.row_mask(rows, n)
        else:
            y = rng.normal(size=n)
            out, buf = _staging._padded_rows(y, rows, None,
                                             dtype=np.float32)
            want = _np_pad(y.astype(np.float32), rows, 0)
        assert out.tobytes() == want.tobytes() and out.dtype == np.float32
        assert buf is not None and pool.stats()["buffers"] == 0
        pool.give_back(buf)
    assert pool.stats() == {"buffers": 1, "bytes": 4 * rows}


def test_the_pool_keeps_to_its_bound_and_evicts_the_least_recently_used():
    pool = _PadPool(5 * MB)
    sizes = [2 * MB, MB, 2 * MB + 8, MB + 8]
    bufs = [pool.take(s)[0] for s in sizes]
    for buf in bufs[:3]:
        pool.give_back(buf)
        assert pool.stats()["bytes"] <= pool.max_bytes
    # 2 + 1 + 2 MB are over the bound: the first given back went
    assert pool.stats() == {"buffers": 2, "bytes": 3 * MB + 8}
    got, warm = pool.take(2 * MB)
    assert got is not bufs[0] and not warm
    again, warm = pool.take(MB)
    assert again is bufs[1] and warm           # a use makes it the newest
    pool.give_back(again)
    pool.give_back(bufs[3])                    # 2 MB + 8, 1 MB, 1 MB + 8
    pool.give_back(np.empty(MB + 16, np.uint8))
    assert pool.stats() == {"buffers": 3, "bytes": 3 * MB + 24}
    assert pool.take(2 * MB + 8)[1] is False   # the oldest had gone
    assert pool.take(MB)[0] is bufs[1]
    # a buffer over the whole bound is never kept
    pool.give_back(np.empty(6 * MB, np.uint8))
    assert pool.stats()["bytes"] <= pool.max_bytes
    assert pool.take(6 * MB)[1] is False


def test_eight_threads_staging_at_once_get_distinct_buffers(pool):
    """Eight pads of one shape in flight together: eight buffers, none
    shared, each holding its own thread's rows; then all eight are kept
    (16 MB of the fixture's 64) and the next eight pads are all warm."""
    import sys
    rows = meshlib.bucket_rows(250_000, 8)
    rng = np.random.default_rng(49)
    arrays = [_block(rng, 250_000 - 3 * i, 2, np.float32, False)
              for i in range(8)]
    barrier = threading.Barrier(8)
    got = [None] * 8

    def stage(i):
        barrier.wait(timeout=60)
        padded, buf = _staging._padded_rows(arrays[i], rows, None)
        barrier.wait(timeout=60)        # all eight hold theirs at once
        got[i] = (padded.copy(), buf)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for round_warm in (False, True):
            threads = [threading.Thread(target=stage, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive()
            assert len({buf.ctypes.data for _, buf in got}) == 8
            for a, (padded, buf) in zip(arrays, got):
                assert padded.tobytes() == _np_pad(a, rows, 0).tobytes()
            if round_warm:
                assert pool.stats()["buffers"] == 0
            for _, buf in got:
                pool.give_back(buf)
            assert pool.stats()["buffers"] == 8
    finally:
        sys.setswitchinterval(interval)
