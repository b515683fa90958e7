"""Test harness: run on a virtual 8-device CPU mesh.

Real multi-chip hardware is not available in CI; the sharding/collective
paths are validated on a host-local 8-device mesh the same way the course
relies on seeded determinism instead of a cluster (SURVEY §4).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Tier-1 compiles XLA:CPU programs and points `sml.compile.cacheDir` (and
# with it the prewarm manifest) at per-test directories. A compile cache
# placed from outside (JAX_COMPILATION_CACHE_DIR) wins over that conf key
# and is meant for the program on the chip, so the tests run without it.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402

# Tier-1 is a CPU suite whatever the machine holds: forcing the platform
# here (as JAX_PLATFORMS=cpu on the command line also does) keeps a test
# process from claiming an accelerator it would then hold for the run.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def spark():
    from sml_tpu import TpuSession
    return TpuSession.builder.appName("tests").getOrCreate()


@pytest.fixture()
def airbnb_pdf():
    """Synthetic SF-Airbnb-like dataset (the real one is blob-hosted and not
    redistributable in-tree); schema mirrors the course's cleaned table."""
    rng = np.random.default_rng(7)
    n = 2000
    neighbourhoods = ["Mission", "SoMa", "Sunset", "Richmond", "Castro", "Noe Valley"]
    room_types = ["Entire home/apt", "Private room", "Shared room"]
    bedrooms = rng.integers(0, 5, n).astype(float)
    accommodates = (bedrooms * 2 + rng.integers(1, 3, n)).astype(float)
    price = np.round(
        np.exp(4.0 + 0.35 * bedrooms + 0.08 * accommodates + rng.normal(0, 0.4, n)), 2)
    pdf = pd.DataFrame({
        "id": np.arange(n, dtype=np.int64),
        "neighbourhood_cleansed": rng.choice(neighbourhoods, n),
        "room_type": rng.choice(room_types, n, p=[0.6, 0.3, 0.1]),
        "bedrooms": bedrooms,
        "bathrooms": rng.choice([1.0, 1.5, 2.0, 2.5], n),
        "accommodates": accommodates,
        "number_of_reviews": rng.integers(0, 300, n).astype(float),
        "review_scores_rating": np.clip(rng.normal(93, 6, n), 20, 100),
        "minimum_nights": rng.integers(1, 30, n).astype(float),
        "price": price,
    })
    return pdf


@pytest.fixture()
def airbnb_df(spark, airbnb_pdf):
    return spark.createDataFrame(airbnb_pdf)


def _same_boosted_fit(p_a, p_b, rmse_a, rmse_b) -> list:
    """What fails of "two layouts fit the same boosted ensemble"; empty
    when nothing does. The two layouts are two compilations that sum a
    histogram in another order, and a split whose two best candidates tie
    to the last ulp may fall either way. Read on test_multichip's and
    test_hierarchical's table (8 rounds of depth 4, XLA:CPU), 8 shards
    against 1 and 4 host groups against 1 alike: rounds 0-2 are the same
    trees, one node of round 3 takes the neighbouring bin (a tie), and
    every later round's leaves follow from that: median |difference|
    1.6e-5, 1.9 % of the rows beyond 1e-3 (those the moved threshold
    re-routes, up to 0.31), rmse apart by 8.2e-5 of itself. The same
    predictions rounded to bfloat16: median 2.1e-3, 69 % of the rows
    beyond 1e-3.

    - the median row agrees to 1e-4 (six times the sound reading, a
      twentieth of the control's): most rows take the same path through
      every tree, and differ by the order of float32 sums alone;
    - at most 5 % of the rows differ by more than 1e-3 (sound 1.9 %,
      control 69 %): a tie that falls the other way re-routes the rows
      between two neighbouring thresholds of one node, not a layout's
      worth of them. Before r6, when the shard index was folded into the
      sampling key, every row differed;
    - the two fits are equally good: rmse within 1e-3 of itself (a tie is
      a tie because both splits gain the same)."""
    gap = np.abs(np.asarray(p_a, np.float64) - np.asarray(p_b, np.float64))
    failed = []
    if not np.median(gap) <= 1e-4:
        failed.append(f"median gap {np.median(gap)}")
    if not np.mean(gap > 1e-3) <= 0.05:
        failed.append(f"{np.mean(gap > 1e-3):.3f} of the rows beyond 1e-3")
    if not abs(rmse_a - rmse_b) <= 1e-3 * abs(rmse_b):
        failed.append(f"rmse {rmse_a} against {rmse_b}")
    return failed


@pytest.fixture(scope="session")
def same_boosted_fit():
    """`_same_boosted_fit`, for the layout-parity tests of boosted fits
    (test_multichip, test_hierarchical)."""
    return _same_boosted_fit


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running perf/scale tests")
