"""Spark randomSplit sampler parity (frame/sampling.py).

Layers of evidence, mirroring the Murmur3 anchoring strategy:
- HARD-CODED golden vectors for hashSeed / XORShiftRandom.nextDouble
  (the published algorithm in core/.../util/random/XORShiftRandom.scala,
  64-byte hash buffer included), cross-derived through the independent
  native murmur3 kernel — the pure-python reference AND the native
  kernel must reproduce them bit-for-bit;
- pinned randomSplit row-index sets for fixed partition layouts;
- structural properties Spark documents and the course demonstrates
  (`ML 02:38-52`): determinism, disjoint+exhaustive cells,
  partition-layout sensitivity, per-partition local sort.
"""

import numpy as np
import pandas as pd
import pytest

from sml_tpu.frame.sampling import (XORShiftRandom, hash_seed,
                                    partition_uniforms, presplit_sort)

# HARD-CODED hashSeed golden vectors (NOT recomputed from hash_seed at
# import time — a tautological pin can never catch a divergence). The
# values are XORShiftRandom.hashSeed over the 64-byte buffer Spark
# actually hashes (ByteBuffer.allocate(java.lang.Long.SIZE) allocates 64
# BYTES — the constant is in bits — so the 8 big-endian seed bytes ride
# with 56 zeros and length-64 finalization), cross-generated from the
# repo's independent C++ murmur3 kernel (native/murmur3.cc, itself
# anchored against the course's Spark hash() constants by
# tests/test_hashing.py) composed per the published hashSeed algorithm.
HASH_SEED_VECTORS = {
    0: 0x427B0291EEA8D4AE,
    1: 0xEB35A34DF420ED6F,
    42: 0xCEA176B6C35E99CF,
    12345: 0x1A5B3ACFF3616EB8,
}

# first nextDouble draws of the hashSeed-scrambled XORShift stream —
# java.util.Random's two-word construction over next(26)/next(27)
NEXT_DOUBLE_VECTORS = {
    0: [0.8446490682263027, 0.4048454303385226,
        0.5871875724155838, 0.8865128837019473],
    42: [0.6661236774413726, 0.8583151351252906,
         0.9139963682495181, 0.8664942556157945],
    12345: [0.3217855146445381, 0.5926558057691951,
            0.3530876039804548, 0.18715752944048802],
}


def test_hash_seed_matches_pinned_goldens():
    for s, v in HASH_SEED_VECTORS.items():
        assert hash_seed(s) == v, f"hashSeed({s}) diverged from pin"
        assert 0 <= v < (1 << 64)
    # distinct seeds scramble to distinct states
    assert len(set(HASH_SEED_VECTORS.values())) == len(HASH_SEED_VECTORS)


def test_hash_seed_matches_independent_murmur3_kernel():
    """Re-derive hashSeed through the independent C++ murmur3 (the hash()
    kernel anchored by test_hashing.py), composing the published
    algorithm: low = mm3(buf64, arraySeed); high = mm3(buf64, low)."""
    import ctypes

    from sml_tpu.native.build import load_library
    lib = load_library("murmur3")
    if lib is None:
        pytest.skip("native murmur3 kernel unavailable")
    lib.mm3_hash_one_bytes.argtypes = [ctypes.c_char_p, ctypes.c_longlong,
                                       ctypes.c_int32]
    lib.mm3_hash_one_bytes.restype = ctypes.c_int32
    for s in (0, 1, 42, 977, 12345, 2**31 - 1):
        buf = (s & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "big") + b"\x00" * 56
        low = lib.mm3_hash_one_bytes(
            buf, 64, ctypes.c_int32(0x3C074A61).value) & 0xFFFFFFFF
        high = lib.mm3_hash_one_bytes(
            buf, 64, ctypes.c_int32(
                low - (1 << 32) if low >= (1 << 31) else low).value) \
            & 0xFFFFFFFF
        assert hash_seed(s) == ((high << 32) | low)


def test_next_double_matches_pinned_goldens():
    for s, want in NEXT_DOUBLE_VECTORS.items():
        rng = XORShiftRandom(s)
        got = [rng.next_double() for _ in range(len(want))]
        assert got == want, f"nextDouble stream for seed {s} diverged"


def test_next_double_reference_properties():
    rng = XORShiftRandom(42)
    draws = [rng.next_double() for _ in range(1000)]
    assert all(0.0 <= d < 1.0 for d in draws)
    # java.util.Random.nextDouble has 53-bit resolution
    assert len(set(draws)) == 1000
    # mean of 1000 uniforms within loose bounds
    assert 0.4 < float(np.mean(draws)) < 0.6


# pinned randomSplit row-index sets: 100 rows [0..99], fixed partition
# layouts — the whole pipeline (pre-split sort → hashSeed → XORShift
# stream → BernoulliCellSampler cells) frozen as observable output. Any
# change to any stage moves these sets.
SPLIT_PINS = [
    # (num_partitions, weights, seed, sorted row ids of the LAST cell)
    (2, [0.8, 0.2], 42,
     [1, 2, 3, 13, 16, 35, 52, 55, 62, 68, 73, 80, 81, 82, 84, 85, 88,
      89, 91, 94, 99]),
    (4, [0.75, 0.25], 7,
     [0, 8, 9, 14, 15, 17, 21, 22, 23, 27, 29, 30, 38, 40, 41, 42, 45,
      47, 49, 56, 58, 59, 61, 66, 77, 83, 97]),
]


def test_random_split_row_sets_match_pins():
    from sml_tpu.frame.dataframe import DataFrame
    pdf = pd.DataFrame({"a": np.arange(100, dtype=float)})
    for nparts, weights, seed, want in SPLIT_PINS:
        df = DataFrame.from_pandas(pdf, num_partitions=nparts)
        cells = df.randomSplit(weights, seed=seed)
        got = sorted(int(v) for v in cells[-1].toPandas()["a"])
        assert got == want, \
            f"randomSplit pin drifted (parts={nparts}, seed={seed})"


def test_native_kernel_matches_reference():
    from sml_tpu.frame.sampling import _xorshift_lib
    assert _xorshift_lib() is not None, \
        "native xorshift kernel failed to build — test would be tautological"
    for seed in (0, 1, 42, 977, 2**31 - 1):
        ref = XORShiftRandom(seed)
        expect = np.array([ref.next_double() for _ in range(257)])
        got = partition_uniforms(seed, 0, 257)
        np.testing.assert_array_equal(got, expect)


def test_partition_uniforms_seed_offset():
    """Spark seeds each partition's sampler with seed + partitionIndex."""
    np.testing.assert_array_equal(partition_uniforms(40, 2, 64),
                                  partition_uniforms(42, 0, 64))


def test_split_cells_disjoint_exhaustive(spark):
    pdf = pd.DataFrame({"a": np.arange(10_000, dtype=float),
                        "b": np.arange(10_000) % 7})
    df = spark.createDataFrame(pdf)
    a, b, c = df.randomSplit([0.5, 0.3, 0.2], seed=42)
    pa, pb, pc = a.toPandas(), b.toPandas(), c.toPandas()
    assert len(pa) + len(pb) + len(pc) == len(pdf)
    seen = np.concatenate([pa["a"], pb["a"], pc["a"]])
    assert len(np.unique(seen)) == len(pdf)
    # weights respected within sampling noise
    assert abs(len(pa) / len(pdf) - 0.5) < 0.02


def test_split_deterministic_and_memoized(spark):
    pdf = pd.DataFrame({"a": np.arange(5000, dtype=float)})
    df = spark.createDataFrame(pdf)
    t1, _ = df.randomSplit([0.8, 0.2], seed=42)
    t2, _ = df.randomSplit([0.8, 0.2], seed=42)
    assert t1 is t2  # plan-cache reuse of identical (weights, seed)
    t3, _ = df.randomSplit([0.8, 0.2], seed=43)
    assert t3 is not t1
    assert sorted(t1.toPandas()["a"]) != sorted(t3.toPandas()["a"])


def test_split_partition_sensitivity(spark):
    """The course's ML 02 lesson: same seed, different partition layout,
    different rows — because the per-partition RNG stream changes."""
    pdf = pd.DataFrame({"a": np.arange(20_000, dtype=float)})
    from sml_tpu.frame.dataframe import DataFrame
    df4 = DataFrame.from_pandas(pdf, num_partitions=4)
    df8 = DataFrame.from_pandas(pdf, num_partitions=8)
    a4, _ = df4.randomSplit([0.8, 0.2], seed=42)
    a8, _ = df8.randomSplit([0.8, 0.2], seed=42)
    s4 = set(a4.toPandas()["a"])
    s8 = set(a8.toPandas()["a"])
    assert s4 != s8
    # but both are deterministic for their layout
    assert set(df4.randomSplit([0.8, 0.2], seed=42)[0].toPandas()["a"]) == s4


def test_presplit_sort_orders_rows_nulls_first():
    pdf = pd.DataFrame({"x": [3.0, np.nan, 1.0, 2.0],
                        "s": ["d", "b", "c", "a"]})
    out = presplit_sort(pdf)
    assert np.isnan(out["x"].iloc[0])
    assert list(out["x"].iloc[1:]) == [1.0, 2.0, 3.0]


def _split_frames():
    rng = np.random.default_rng(28)
    n = 30_000
    base = pd.DataFrame({
        "flag": rng.choice(["t", "f"], n), "k": rng.integers(0, 19, n) * 1.0,
        "hood": rng.choice(["Mission", "Castro", "Marina", None], n),
        "lat": rng.random(n), "beds": rng.integers(0, 5, n) * 1.0,
        "price": np.round(rng.normal(100, 30, n))})
    again = base.iloc[rng.integers(0, n, 2000)].copy()
    again.iloc[:1000, again.columns.get_loc("price")] += 1.0
    few_ties = pd.concat([base, again]).sample(frac=1.0, random_state=1)
    nulls = base.copy()
    nulls.loc[rng.random(n) < 0.02, "lat"] = np.nan
    nulls.loc[rng.random(n) < 0.02, "k"] = np.nan
    runs = pd.DataFrame({"a": np.repeat(np.arange(n // 4), 4) * 1.0,
                         "b": rng.normal(size=n)})[::-1]
    return {"told apart by five of six columns": base,
            "a few rows tie on them": few_ties,
            "nulls among them": nulls,
            "every row a duplicate": base[["flag", "k"]],
            "ties the sample cannot see": runs,
            "strings as StringDtype": base.astype({"flag": "string",
                                                   "hood": "string"}),
            "five rows": base.iloc[:5], "no row": base.iloc[:0]}


@pytest.mark.parametrize("case", list(_split_frames()))
def test_presplit_order_is_the_sort_by_every_column(case):
    """`_sort_order` sorts by the leading columns that tell the rows apart
    and settles what still ties by every column: the permutation is the
    full stable sort's, nulls first, row for row."""
    from sml_tpu.frame import sampling
    pdf = _split_frames()[case]
    want = pdf.reset_index(drop=True).sort_values(
        list(pdf.columns), kind="stable", na_position="first").index.to_numpy()
    got = sampling._sort_order(pdf)
    assert np.array_equal(got, want)


def test_legacy_sampler_conf(spark):
    from sml_tpu.conf import GLOBAL_CONF
    pdf = pd.DataFrame({"a": np.arange(4000, dtype=float)})
    df = spark.createDataFrame(pdf)
    spark_rows = set(df.randomSplit([0.8, 0.2], seed=7)[0].toPandas()["a"])
    GLOBAL_CONF.set("sml.split.sampler", "legacy")
    try:
        df2 = spark.createDataFrame(pdf)
        legacy_rows = set(
            df2.randomSplit([0.8, 0.2], seed=7)[0].toPandas()["a"])
    finally:
        GLOBAL_CONF.set("sml.split.sampler", "spark")
    assert legacy_rows != spark_rows  # distinct documented mechanisms


def test_presplit_memo_keeps_the_order_not_the_partition(spark):
    """The memo holds 8 bytes a row (the permutation), whatever the
    partition's width, and does not keep a partition alive: a cached
    frame's later splits sort nothing again, and a partition that dies
    takes its entry along. (It held the source and a sorted copy, so a
    frame wider than the bound re-sorted at every split.)"""
    import gc

    from sml_tpu.frame import sampling
    rng = np.random.default_rng(3)
    n = 20_000
    pdf = pd.DataFrame({"a": rng.normal(size=n),
                        "s": rng.choice(["x", "y", "z"], n),
                        **{f"w{i}": rng.normal(size=n) for i in range(20)}})
    df = spark.createDataFrame(pdf).repartition(4).cache()
    df.count()
    sorts = []
    real = sampling._sort_order
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sampling, "_sort_order",
                      lambda p: sorts.append(len(p)) or real(p))
        first = df.randomSplit([0.8, 0.2], seed=1)
        rows = [part.count() for part in first]
        assert sum(rows) == n and len(sorts) == 4
        for seed in (2, 3, 4):
            again = df.randomSplit([0.8, 0.2], seed=seed)
            assert sum(part.count() for part in again) == n
        assert len(sorts) == 4, "a cached frame's partitions sort once"
    mine = [k for k, (ref, _, _) in sampling._sort_memo.items()
            if any(ref() is p for p in df._parts)]
    assert len(mine) == 4
    assert sum(sampling._sort_memo[k][2] for k in mine) == 8 * n
    # the split is what the sorted partition would give, row for row
    part = df._parts[0]
    u = sampling.partition_uniforms(1, 0, len(part))
    want = sampling.presplit_sort(part)[u < 0.8].reset_index(drop=True)
    pd.testing.assert_frame_equal(first[0]._parts[0], want)
    # an entry dies with its partition
    loose = pdf.iloc[:100].reset_index(drop=True)
    key = id(loose)
    assert sampling.presplit_order(loose) is not None
    assert key in sampling._sort_memo
    before = sampling._sort_memo_bytes[0]
    del loose
    gc.collect()
    assert key not in sampling._sort_memo
    # (the collection may have taken other dead frames' entries along)
    assert sampling._sort_memo_bytes[0] <= before - 800
    df.unpersist()
    assert not any(k in sampling._sort_memo for k in mine)
