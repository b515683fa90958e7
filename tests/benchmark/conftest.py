"""The harness sets the engine's process-wide configuration (a run owns its
process). A test process is shared with the rest of tier-1, so every module
here hands the configuration back as it found it."""

import gc

import pytest


@pytest.fixture(scope="module", autouse=True)
def engine_state_restored():
    yield
    import bench_tiny  # noqa: F401 — puts the repo root on sys.path
    from benchmark.harness import spec
    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    keys = {"sml.obs.enabled", "sml.profiler.enabled"}
    for entry in spec.load_benchmark(bench_tiny.REPO)["configs"]:
        keys |= set(spec.load_json(
            f"{bench_tiny.REPO}/{entry['file']}").get("conf", {}))
    GLOBAL_CONF.set("sml.obs.enabled", False)   # the recorder follows `set`
    for key in keys:
        GLOBAL_CONF.unset(key)
    obs.reset()
    gc.unfreeze()
