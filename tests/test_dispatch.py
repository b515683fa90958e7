"""Latency-calibrated dispatch policy (parallel/dispatch.py).

The policy itself is pure arithmetic over measured constants, so it is
tested here with a pinned fake calibration of a device this process does
not own (a locally attached chip is never priced): small work routes host, large work routes device, and work that
loses only by its one-time H2D cost triggers background promotion so later
fits ride the chip (VERDICT r2 #1a/#2).
"""

import numpy as np
import pytest

from sml_tpu.conf import GLOBAL_CONF
from sml_tpu.ml import _staging
from sml_tpu.parallel import dispatch, mesh as meshlib
from sml_tpu.parallel.dispatch import WorkHint


@pytest.fixture
def remote_device(monkeypatch):
    """Pretend the default backend is a TPU this process does not own
    (not locally attached), with a slow pinned link calibration — the one
    case `auto` prices."""
    monkeypatch.setattr(dispatch, "_default_backend", lambda: "tpu")
    monkeypatch.setattr(dispatch, "_locally_attached", lambda: False)
    cal = dispatch._Calibration()
    cal._done = True
    cal.rt_fixed = 0.15          # s per dispatch+readback
    cal.h2d_bw = 200e6           # bytes/s
    cal.d2h_bw = 20e6
    monkeypatch.setattr(dispatch, "CALIBRATION", cal)
    yield cal


def test_small_work_routes_host(remote_device):
    route, promote = dispatch.decide(WorkHint(flops=1e8, kind="blas"))
    assert route == "host" and not promote


def test_large_work_routes_device(remote_device):
    route, _ = dispatch.decide(WorkHint(flops=1e12, kind="blas"))
    assert route == "device"


def test_h2d_only_loss_requests_promotion(remote_device):
    # device wins decisively on flops (0.15 + 1e11/2e12 = 0.2s vs host
    # 1e11/6e9 = 16.7s) but loses once a 10GB staging transfer is charged
    hint = WorkHint(flops=1e11, kind="blas", in_bytes=1e10)
    route, promote = dispatch.decide(hint)
    assert route == "host" and promote


def test_mode_conf_overrides(remote_device):
    GLOBAL_CONF.set("sml.dispatch.mode", "device")
    try:
        assert dispatch.decide(WorkHint(flops=1.0)) == ("device", False)
        GLOBAL_CONF.set("sml.dispatch.mode", "host")
        assert dispatch.decide(WorkHint(flops=1e15)) == ("host", False)
    finally:
        GLOBAL_CONF.set("sml.dispatch.mode", "auto")


def test_no_hint_routes_device(remote_device):
    assert dispatch.decide(None)[0] == "device"


def test_forced_host_wins_for_unhinted_programs(remote_device):
    """sml.dispatch.mode=host must beat the hint-is-None device fallback —
    'host: always the host mesh' is the conf's contract (ADVICE r3)."""
    GLOBAL_CONF.set("sml.dispatch.mode", "host")
    try:
        assert dispatch.decide(None) == ("host", False)
        assert dispatch.preroute(None) == "host"
    finally:
        GLOBAL_CONF.set("sml.dispatch.mode", "auto")


def test_large_array_fingerprint_sees_point_edits():
    """A >16MB array's staging fingerprint must change when a single
    element changes anywhere — including outside the 16 sampled windows
    (ADVICE r3 medium: delta UPDATE then re-fit must not reuse stale
    device data)."""
    rng = np.random.default_rng(7)
    a = rng.normal(size=(6_000_000,)).astype(np.float32)  # 24MB
    assert a.nbytes > _staging._FULL_HASH_MAX_BYTES
    k0 = _staging._content_key(a)
    # flip one element strictly between two sampled windows, asserted so:
    # without the whole-array checksum this edit is invisible to the key
    edit = 1_000_000
    byte = edit * a.itemsize
    starts = np.linspace(0, a.nbytes - _staging._SAMPLE_WINDOW,
                         _staging._SAMPLE_COUNT).astype(np.int64)
    assert not any(s <= byte < s + _staging._SAMPLE_WINDOW
                   and s <= byte + a.itemsize - 1 < s + _staging._SAMPLE_WINDOW
                   for s in starts) and not any(
        s <= byte < s + _staging._SAMPLE_WINDOW for s in starts)
    b = a.copy()
    b[edit] += 1.0
    assert _staging._content_key(b) != k0
    # row permutation outside every window must also change the key — a
    # commutative checksum would serve stale pre-shuffle device data
    # against freshly-extracted labels (r4 review)
    c = a.copy().reshape(1_500_000, 4)
    c[[100_000, 100_001]] = c[[100_001, 100_000]]
    c = np.ascontiguousarray(c.reshape(-1))
    assert _staging._content_key(c) != k0
    # compensating ± edits of two aligned words must not cancel
    d = a.copy()
    dv = d.view(np.uint64)
    dv[500_000] += np.uint64(999)
    dv[500_007] -= np.uint64(999)
    assert _staging._content_key(d) != k0
    # deterministic across identical copies
    assert _staging._content_key(a.copy()) == k0


def test_cpu_backend_short_circuits(monkeypatch):
    monkeypatch.setattr(dispatch, "_default_backend", lambda: "cpu")
    assert dispatch.decide(WorkHint(flops=1.0))[0] == "device"


def test_locally_attached_chip_is_never_priced(monkeypatch):
    """PR 21: a chip attached to this host takes every program in `auto`
    — decided from the devices (all owned by this process), not from a
    timed round trip, so calibration never runs and a slow first dispatch
    cannot flip the route. mode=host still forces the host."""
    monkeypatch.setattr(dispatch, "_default_backend", lambda: "tpu")
    cal = dispatch._Calibration()   # NOT done: ensure() would measure
    monkeypatch.setattr(dispatch, "CALIBRATION", cal)
    assert dispatch._locally_attached()  # single-process test run
    assert dispatch.decide(WorkHint(flops=1.0)) == ("device", False)
    assert dispatch._preroute(WorkHint(flops=1.0)) == ("device",
                                                       "local-chip")
    assert not cal._done
    GLOBAL_CONF.set("sml.dispatch.mode", "host")
    try:
        assert dispatch.decide(WorkHint(flops=1e15)) == ("host", False)
    finally:
        GLOBAL_CONF.set("sml.dispatch.mode", "auto")


def test_host_mesh_says_why_when_cpu_backend_is_excluded(monkeypatch):
    """A process started with JAX_PLATFORMS naming only the accelerator
    has no CPU backend: the host route must fail with a message that
    names the cause and the way out, not jax's bare lookup error."""
    import jax
    monkeypatch.setattr(dispatch, "_host_mesh", None)

    def no_cpu(backend=None):
        raise RuntimeError("Unknown backend cpu")

    monkeypatch.setattr(jax, "devices", no_cpu)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS"):
        dispatch.host_mesh()


def test_route_mesh_probes_staging_and_promotes(remote_device):
    """Unstaged big input → host route + async promotion; once staged, the
    same call routes device (the H2D term vanishes)."""
    GLOBAL_CONF.set("sml.dispatch.autoPromote", True)
    X = np.random.default_rng(0).normal(size=(4096, 64)).astype(np.float32)
    # flops chosen so the device wins decisively once resident (host
    # 5e9/6e9 = 0.83s vs resident 0.15s) but loses while X's ~1MB H2D is
    # charged at the test's 1MB/s bandwidth (+1.05s)
    remote_device.h2d_bw = 1e6
    hint = WorkHint(flops=5e9, kind="blas")
    m1, r1 = _staging._route_mesh(hint, (X,))
    assert r1 == "host" and dispatch.is_host_mesh(m1)
    # the promotion staged X under the device mesh → second probe sees it
    m2, r2 = _staging._route_mesh(hint, (X,))
    assert r2 == "device" and m2 is meshlib.get_mesh()


def test_bucket_rows_buckets_and_divides():
    from sml_tpu.parallel.mesh import bucket_rows
    for n_dev in (1, 4, 8):
        prev = 0
        for n in [1, 7, 100, 1000, 40_000, 48_000, 1_000_000]:
            b = bucket_rows(n, n_dev)
            assert b >= n and b % n_dev == 0
            assert b <= max(1.125 * n, n + n_dev + 16)  # ≤12.5% padding
            assert b >= prev
            prev = b
    # nearby sizes share a bucket (the compile-cache point of bucketing)
    assert bucket_rows(40_000, 8) == bucket_rows(40_011, 8)


def test_observed_host_rates_steer_routing(remote_device, monkeypatch):
    """The router's host cost model self-corrects from measured wall times
    (r4: the hard-coded scatter rate over-credited tree traversal 6x and
    routed 13.6s of forest predicts onto the host). An observed slow rate
    must flip a marginal job to the device; fresh state must fall back to
    the bootstrap constant."""
    monkeypatch.setattr(dispatch, "OBSERVED_HOST", dispatch._ObservedRates())
    hint = WorkHint(flops=2e8, kind="traverse", out_bytes=256.0)
    # bootstrap: 2e8 ops at 2.5e8 ops/s = 0.8s host vs ~0.15s device
    assert dispatch.host_time(hint) == pytest.approx(0.8)
    # a measured FAST host (1e10 ops/s over real work) flips hostward
    dispatch.OBSERVED_HOST.observe("traverse", 2e10, 2.0)
    assert dispatch.host_time(hint) < 0.05
    assert dispatch.decide(hint)[0] == "host"
    # one compile-inflated sample only dilutes in proportion to its work —
    # the fast big-call evidence still dominates the weighted rate
    dispatch.OBSERVED_HOST.observe("traverse", 2e8, 2.0)
    assert dispatch.decide(hint)[0] == "host"
    # ... but a full window of genuinely slow samples is real evidence
    for _ in range(8):
        dispatch.OBSERVED_HOST.observe("traverse", 2e8, 2.0)
    assert dispatch.decide(hint)[0] == "device"
    # sub-ms, sub-floor, and zero-flop observations are ignored (noise)
    before = dispatch.OBSERVED_HOST.rate("traverse")
    dispatch.OBSERVED_HOST.observe("traverse", 1e9, 1e-5)
    dispatch.OBSERVED_HOST.observe("traverse", 2e7, 1.0)
    dispatch.OBSERVED_HOST.observe("traverse", 0.0, 1.0)
    assert dispatch.OBSERVED_HOST.rate("traverse") == before


def test_route_mesh_stacked_prices_and_promotes_stack_layout(remote_device):
    """The fold-batched fit consumes axis-1-sharded (folds, rows, ...)
    stacks: the router must probe and promote THAT layout ("stack" keys),
    not the per-fold 2-D layout — otherwise residency is discounted for
    arrays the program never reads and promotion uploads dead copies
    (r4 review)."""
    GLOBAL_CONF.set("sml.dispatch.autoPromote", True)
    stack = np.random.default_rng(1).normal(
        size=(3, 4096, 32)).astype(np.float32)
    remote_device.h2d_bw = 1e6
    hint = WorkHint(flops=5e9, kind="blas")
    m1, r1 = _staging._route_mesh(hint, (stack,), stacked=True)
    assert r1 == "host" and dispatch.is_host_mesh(m1)
    # promotion staged the STACK layout → the stacked probe now sees it
    m2, r2 = _staging._route_mesh(hint, (stack,), stacked=True)
    assert r2 == "device" and m2 is meshlib.get_mesh()
    # the 2-D probe must NOT see the stacked entry as resident (a wrongly
    # shared key would zero the H2D term and flip this to device)
    remote_device.h2d_bw = 2.5e5  # make the unstaged H2D decisive for 0.5MB
    m3, r3 = _staging._route_mesh(hint, (np.ascontiguousarray(stack[0]),),
                                  may_promote=False)
    assert r3 == "host"
    # and the staged stack is row-sharded on axis 1 (fold axis replicated)
    from sml_tpu.ml._staging import stage_stacked_cached
    dev = stage_stacked_cached(stack)
    assert dev.shape == stack.shape
    spec = dev.sharding.spec
    assert spec[1] == meshlib.DATA_AXIS and spec[0] is None


# --------------------------------------------- compile-cache placement (PR 21)
def test_compile_cache_placed_from_outside_wins(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set the cache is there and the
    code sets no other directory: no `jax_compilation_cache_dir` update,
    not even for `sml.compile.cacheDir`."""
    import jax
    env_dir = str(tmp_path / "from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    updates = []
    real_update = jax.config.update

    def spy(name, value):
        updates.append(name)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    prev = GLOBAL_CONF.get("sml.compile.cacheDir")
    try:
        assert dispatch.ensure_compile_cache() == env_dir
        GLOBAL_CONF.set("sml.compile.cacheDir", str(tmp_path / "conf"))
        assert dispatch.ensure_compile_cache() == env_dir
    finally:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        GLOBAL_CONF.set("sml.compile.cacheDir", prev or "")
    assert "jax_compilation_cache_dir" not in updates


def test_compile_cache_defaults_to_checkout_dir(monkeypatch, tmp_path):
    """Unset, the directory is the fixed <checkout>/.jax_cache — never a
    temporary, pid- or time-derived path; `sml.compile.cacheDir` moves it
    and clearing the key restores the default. The second spelling
    (SML_TPU_COMPILE_CACHE) is gone."""
    import inspect
    import os

    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("SML_TPU_COMPILE_CACHE", str(tmp_path / "dead"))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    prev = GLOBAL_CONF.get("sml.compile.cacheDir")
    try:
        GLOBAL_CONF.set("sml.compile.cacheDir", "")
        assert dispatch.ensure_compile_cache() \
            == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir \
            == os.path.join(repo, ".jax_cache")
        GLOBAL_CONF.set("sml.compile.cacheDir", str(tmp_path))
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        GLOBAL_CONF.set("sml.compile.cacheDir", prev or "")
    assert "SML_TPU_COMPILE_CACHE" not in inspect.getsource(dispatch)
