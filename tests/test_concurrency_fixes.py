"""Two-thread regression tests for the races the graftlint concurrency
pass (PR 13) surfaced and fixed — each test hammers the fixed path from
the two roles the static analysis named, asserting the documented
contract holds under interleaving (no AttributeError/TypeError from a
torn check-then-use, no lost reset, no orphaned registration).

These are the runtime twins of the `race-unguarded-shared-write` /
`race-check-then-use` fixtures in tests/test_graftlint.py: the lint
rule proves the *shape* is gone from the tree, these prove the fixed
code actually tolerates the interleavings.
"""

import threading
import time

import numpy as np
import pytest

from sml_tpu.serving._batcher import ScoreFuture


HAMMER = 300


# --------------------------------------------------- ScoreFuture.result
def test_scorefuture_result_error_snapshot_race():
    """`result()` snapshots `_error` before raising: a close() drain and
    the flush worker racing `_set_error`/`_set` must surface EITHER the
    batch error or the value — never an AttributeError/TypeError from
    `_error` flipping between the None-check and the raise."""
    for i in range(HAMMER):
        fut = ScoreFuture(1)
        err = RuntimeError("batch failed")

        def set_error():
            fut._set_error(err)

        def set_value():
            fut._set(np.zeros(1))

        t1 = threading.Thread(target=set_error)
        t2 = threading.Thread(target=set_value)
        # alternate start order to vary the interleaving
        first, second = (t1, t2) if i % 2 else (t2, t1)
        first.start()
        second.start()
        try:
            out = fut.result(timeout=5.0)
            assert isinstance(out, np.ndarray)
        except RuntimeError as e:
            assert e is err
        first.join()
        second.join()


# ------------------------------------------------ StreamingQuery surface
def _bare_query():
    from sml_tpu.streaming.stream import StreamingQuery
    q = object.__new__(StreamingQuery)
    q.recentProgress = []
    q._stop = threading.Event()
    q._exception = None
    q._processed = set()
    return q


def test_stream_lastprogress_snapshot_race():
    """`lastProgress` snapshots `recentProgress`: the trigger thread
    appending between the emptiness check and the [-1] index must never
    turn the property into an IndexError."""
    q = _bare_query()
    stop = threading.Event()

    def appender():
        n = 0
        while not stop.is_set():
            q.recentProgress.append({"n": n})
            n += 1

    t = threading.Thread(target=appender, daemon=True)
    t.start()
    try:
        for _ in range(5000):
            prog = q.lastProgress
            assert prog is None or isinstance(prog, dict)
    finally:
        stop.set()
        t.join()


def test_stream_exception_snapshot_surfaces_cause():
    """`processAllAvailable` raises from a SNAPSHOT of `_exception` —
    the trigger thread publishing the exception then stopping must
    surface the original as the cause, at any interleaving."""
    class _SDF:
        def _list_files(self):
            return ["pending-file"]

    boom = ValueError("trigger died")
    for _ in range(50):
        q = _bare_query()
        q._sdf = _SDF()

        def die():
            q._exception = boom
            q._stop.set()

        t = threading.Thread(target=die)
        t.start()
        with pytest.raises(RuntimeError) as ei:
            q.processAllAvailable()
        assert ei.value.__cause__ is boom
        t.join()


# ------------------------------------------- endpoint drift install/close
def test_endpoint_drift_install_vs_close_no_orphan_registration():
    """`_install_drift` (stage-transition listener thread) and `close`
    both rebind `self._drift` under `_swap_lock`: after a storm of
    concurrent installs and closes ending in a final close, the drift
    registry must hold NO monitor under the endpoint's key (the
    unguarded form could re-register a monitor the close had just torn
    down, leaving an orphan reporting forever)."""
    from sml_tpu.obs import drift as _drift
    from sml_tpu.serving._endpoint import ServingEndpoint

    class _Batcher:
        def close(self):
            pass

    ep = object.__new__(ServingEndpoint)
    ep._name, ep._stage = "race-model", "Production"
    ep._swap_lock = threading.RLock()
    ep._canary_lock = threading.Lock()
    ep._scorer = None          # no baseline -> install takes the None arm
    ep._drift = None
    ep._listener = None
    ep._batcher = _Batcher()
    ep._shadow_pool = None
    ep._closed = False
    key = ep._drift_key()

    # seed a fake registered monitor so both arms have work to do
    fake = object()
    _drift.DRIFT.register(key, fake)
    ep._drift = fake

    stop = threading.Event()
    errors = []

    def installer():
        while not stop.is_set():
            try:
                ep._install_drift()
            except Exception as e:  # pragma: no cover - the assertion
                errors.append(e)

    t = threading.Thread(target=installer, daemon=True)
    t.start()
    try:
        for _ in range(200):
            ep.close()
    finally:
        stop.set()
        t.join()
    ep.close()
    assert not errors
    assert _drift.DRIFT.get(key) is None, \
        "close left an orphaned drift-monitor registration behind"


# --------------------------------------------------------- watchdog reset
def test_watchdog_reset_takes_the_flagger_lock():
    """`Watchdog.reset` zeroes `flagged_total` under `_lock` — the same
    lock the flagger thread increments under — so a reset can no longer
    interleave into an increment and resurrect the dropped count."""
    from sml_tpu.obs._watchdog import Watchdog
    w = Watchdog()
    w._lock.acquire()
    done = threading.Event()

    def resetter():
        w.reset()
        done.set()

    t = threading.Thread(target=resetter, daemon=True)
    t.start()
    time.sleep(0.15)
    assert not done.is_set(), "reset() proceeded without the flagger lock"
    w._lock.release()
    assert done.wait(5.0)
    t.join()
    assert w.flagged_total == 0


# -------------------------------------------- DeviceScorer snapshot reads
def test_kernel_spec_snapshot_race():
    """`DeviceScorer.kernel_spec` snapshots `_kernel_spec`: a serving
    dispatch rebinding it mid-call must never turn the health probe into
    a TypeError(dict(None))."""
    from sml_tpu.ml.inference import DeviceScorer
    sc = object.__new__(DeviceScorer)
    sc._kernel_spec = None
    stop = threading.Event()

    def flipper():
        i = 0
        while not stop.is_set():
            sc._kernel_spec = None if i % 2 else \
                {"kernel": "pallas", "block_rows": 256}
            i += 1

    t = threading.Thread(target=flipper, daemon=True)
    t.start()
    try:
        for _ in range(5000):
            spec = sc.kernel_spec()
            assert spec is None or spec["kernel"] == "pallas"
    finally:
        stop.set()
        t.join()


def test_build_factorized_snapshot_race():
    """`_build_factorized` snapshots `_featurizer` (the PR-12 family):
    a prefetch thread nulling the featurizer between the width check and
    the source walk must yield None, never AttributeError."""
    from sml_tpu.ml.inference import DeviceScorer

    class _Featurizer:
        width = 0
        sources = []

    sc = object.__new__(DeviceScorer)
    sc._params = (np.zeros(0),)
    sc._featurizer = _Featurizer()
    stop = threading.Event()

    def flipper():
        i = 0
        while not stop.is_set():
            sc._featurizer = None if i % 2 else _Featurizer()
            i += 1

    t = threading.Thread(target=flipper, daemon=True)
    t.start()
    try:
        for _ in range(5000):
            out = sc._build_factorized()
            assert out is None or out == ([], [])
    finally:
        stop.set()
        t.join()


# ------------------------------- StreamingQuery shutdown semantics (PR 14)
def _write_parquet(path, values):
    import pandas as pd
    pd.DataFrame({"a": values}).to_parquet(path)


def test_forced_stop_mid_trigger_flushes_checkpoint_exactly_once(
        spark, tmp_path, monkeypatch):
    """A query killed BETWEEN its sink write landing and its checkpoint
    save must still flush the checkpoint exactly once (the `_run`
    finally covers the gap via the dirty flag), so a resumed query on
    the same checkpointLocation never reprocesses the committed
    micro-batch — the duplicate-on-resume bug the continuous trainer's
    supervisor would otherwise inherit."""
    from sml_tpu.streaming.stream import StreamingQuery

    src_dir = tmp_path / "src"
    src_dir.mkdir()
    _write_parquet(src_dir / "p0.parquet", [1.0, 2.0, 3.0])
    ckpt = str(tmp_path / "ckpt")

    class Forced(RuntimeError):
        pass

    orig_save = StreamingQuery._save_checkpoint
    calls = []
    effective = []

    def flaky_save(self):
        calls.append(1)
        if len(calls) == 1:
            # the forced stop: the write landed, the save did not
            raise Forced("killed between sink write and checkpoint save")
        orig_save(self)
        effective.append(1)

    monkeypatch.setattr(StreamingQuery, "_save_checkpoint", flaky_save)
    sdf = spark.readStream.schema("a double").parquet(str(src_dir))
    q = sdf.writeStream.format("memory").queryName("forced_stop_q") \
        .option("checkpointLocation", ckpt).start()
    assert q.awaitTermination(10)
    assert isinstance(q.exception(), Forced)
    assert effective == [1], "finally must flush the dirty checkpoint ONCE"
    monkeypatch.setattr(StreamingQuery, "_save_checkpoint", orig_save)

    # resume on the same checkpoint: the committed batch must NOT
    # reprocess (its file is recorded; nothing new to trigger on)
    q2 = sdf.writeStream.format("memory").queryName("forced_stop_q2") \
        .option("checkpointLocation", ckpt) \
        .trigger(availableNow=True).start()
    q2.awaitTermination(10)
    assert q2.exception() is None
    assert q2.recentProgress == []


def test_clean_trigger_saves_checkpoint_exactly_once(spark, tmp_path,
                                                     monkeypatch):
    """The exactly-once contract's other half: an UNinterrupted trigger
    must not double-save through the finally flush."""
    from sml_tpu.streaming.stream import StreamingQuery

    src_dir = tmp_path / "src"
    src_dir.mkdir()
    _write_parquet(src_dir / "p0.parquet", [1.0, 2.0])
    orig_save = StreamingQuery._save_checkpoint
    saves = []

    def counting_save(self):
        saves.append(1)
        orig_save(self)

    monkeypatch.setattr(StreamingQuery, "_save_checkpoint", counting_save)
    sdf = spark.readStream.schema("a double").parquet(str(src_dir))
    q = sdf.writeStream.format("memory").queryName("clean_stop_q") \
        .option("checkpointLocation", str(tmp_path / "ckpt")) \
        .trigger(availableNow=True).start()
    q.awaitTermination(10)
    assert q.exception() is None
    assert saves == [1]


def test_await_any_termination_releases_on_one_termination(spark,
                                                           tmp_path,
                                                           monkeypatch):
    """`StreamManager.awaitAnyTermination` must return when ANY query
    terminates (the pre-fix loop waited for ALL active queries to
    drain) and honor its timeout with a bool result."""
    from sml_tpu.streaming import stream as stream_mod

    # isolate from queries other tests left in the module registry
    monkeypatch.setattr(stream_mod, "_active_queries", [])
    src_dir = tmp_path / "src"
    src_dir.mkdir()
    _write_parquet(src_dir / "p0.parquet", [1.0])
    sdf = spark.readStream.schema("a double").parquet(str(src_dir))

    def start(name):
        return sdf.writeStream.format("memory").queryName(name).start()

    q1, q2 = start("await_q1"), start("await_q2")
    try:
        # both alive: a short timeout must come back False, not hang
        assert spark.streams.awaitAnyTermination(timeout=0.3) is False

        done = []
        waiter = threading.Thread(
            target=lambda: done.append(
                spark.streams.awaitAnyTermination(timeout=10)),
            daemon=True)
        waiter.start()
        time.sleep(0.2)
        q1.stop()          # ONE termination must release the wait
        waiter.join(timeout=10)
        assert done == [True]
        assert q2.isActive  # the other query was never awaited on
    finally:
        q1.stop()
        q2.stop()
