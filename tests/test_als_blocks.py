"""ALS at a size its statistics do not fit: the normal equations built by
blocks of rows (`ml/recommendation.py`), held on the CPU against the plain
float64 reference of the benchmark (`benchmark/reference/als.py`) on skewed
ratings, and the host path (dense ids, the two stable orders, the bounds)
held to the bit against `np.unique` and `np.argsort(kind="stable")`."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.data import movielens  # noqa: E402
from benchmark.harness import runner  # noqa: E402
from benchmark.reference import als as ref  # noqa: E402
from sml_tpu.ml import recommendation as R  # noqa: E402
from sml_tpu.ml.recommendation import ALS  # noqa: E402
from sml_tpu.parallel import mesh as meshlib  # noqa: E402

COLS = dict(userCol="userId", itemCol="movieId", ratingCol="rating")
#: the generator's own skews are `ml-25m`'s; a table of 30,000 ratings
#: needs steeper ones for segments of hundreds beside segments of one
SHAPE = {"rows": 30_000, "users": 300, "items": 800, "max_item_id": 5000,
         "item_offset": 10.0, "item_skew": 1.5, "user_offset": 5.0}


@pytest.fixture(scope="module")
def skewed():
    """30,000 ratings of `ml-25m`'s kind: the busiest user and the
    most-rated movie hold hundreds, most movies a handful."""
    return movielens.make(SHAPE, 11)


@pytest.fixture
def blocks(monkeypatch):
    """Sets the rows of a block, which the program otherwise derives from
    the device's memory."""
    def rows(n):
        monkeypatch.setattr(R, "_block_rows", lambda rank: n)
    return rows


def _gaps(model, pdf, rank, max_iter, reg=0.1, seed=42, nonneg=False):
    """(largest factor gap, largest prediction gap over the table's own
    pairs) of a fitted model against the float64 reference."""
    u, i, r = (pdf[c].to_numpy() for c in ("userId", "movieId", "rating"))
    want = ref.fit(u, i, r, rank, max_iter, reg, seed, nonneg)
    assert np.array_equal(model._user_ids, want["user_ids"])
    assert np.array_equal(model._item_ids, want["item_ids"])
    got = {"user_ids": model._user_ids, "item_ids": model._item_ids,
           "user_factors": model._uf, "item_factors": model._if}
    factor = max(np.abs(model._uf - want["user_factors"]).max(),
                 np.abs(model._if - want["item_factors"]).max())
    return factor, np.abs(ref.predict(got, u, i)
                          - ref.predict(want, u, i)).max()


# ------------------------------------------------- the system, by blocks
@pytest.mark.parametrize("devices,block,rank,nonneg", [
    (1, 64, 12, False), (8, 64, 12, False), (8, 1000, 4, False),
    (1, 96, 4, True), (8, 64, 4, True), (1, 1 << 20, 12, False)])
def test_the_blocked_fit_is_the_references(spark, skewed, blocks, devices,
                                           block, rank, nonneg):
    """Blocks of 64 rows under segments of hundreds: a user's or a
    movie's rows span three blocks and more, and on the eight-device mesh
    a shard's end besides; one block for the whole table is the same fit."""
    counts = np.bincount(skewed["movieId"])
    assert counts.max() > 3 * 64 and (counts[counts > 0] <= 2).any()
    assert np.bincount(skewed["userId"]).max() > 3 * 64
    blocks(block)
    with meshlib.use_mesh(meshlib.build_mesh(devices)):
        model = ALS(rank=rank, maxIter=3, regParam=0.1, seed=42,
                    nonnegative=nonneg, **COLS).fit(
                        spark.createDataFrame(skewed))
    factor, prediction = _gaps(model, skewed, rank, 3, nonneg=nonneg)
    assert factor < 2e-4 and prediction < 2e-4, (factor, prediction)
    if nonneg:
        assert model._uf.min() >= 0 and model._if.min() >= 0
        assert (model._uf == 0).any() or (model._if == 0).any()


@pytest.mark.parametrize("block", [64, 48, 128])
def test_a_blocks_end_on_a_segments_end_and_inside_one(spark, blocks, block):
    """Every user rates exactly 64 movies: blocks of 64 rows end where a
    user's rows end, of 128 hold two users whole, of 48 end inside."""
    rng = np.random.default_rng(5)
    users, per = 90, 64
    pdf = pd.DataFrame({
        "userId": np.repeat(np.arange(users), per) + 1,
        "movieId": np.concatenate([rng.choice(150, per, replace=False)
                                   for _ in range(users)]) * 3 + 2,
        "rating": rng.integers(1, 11, users * per) / 2.0})
    blocks(block)
    with meshlib.use_mesh(meshlib.build_mesh(1)):
        model = ALS(rank=4, maxIter=3, regParam=0.1, seed=3, **COLS).fit(
            spark.createDataFrame(pdf))
    factor, prediction = _gaps(model, pdf, 4, 3, seed=3)
    assert factor < 1e-4 and prediction < 1e-4, (factor, prediction)


@pytest.mark.parametrize("devices", [1, 8])
def test_padding_rows_and_entities_without_ratings_stay_inert(devices):
    """The program's own shapes: more entities than the ratings name (the
    counts on `bucket_rows`' grid, or a split that lacks a movie) and rows
    padded to the grid and to whole blocks. An entity with no rating is 0
    and moves no other."""
    from sml_tpu.ml._staging import data_parallel
    rng = np.random.default_rng(8)
    n, U, I, rank = 5000, 40, 30, 4
    u = rng.integers(0, U, n).astype(np.int32)
    i = rng.integers(0, I - 1, n).astype(np.int32)      # movie I - 1: none
    i[i == 7] = 8                                        # nor movie 7
    r = rng.integers(1, 11, n).astype(np.float32) / 2
    uf0, if0 = (np.abs(rng.standard_normal((k, rank))).astype(np.float32)
                for k in (U + 5, I + 3))
    with meshlib.use_mesh(meshlib.build_mesh(devices)):
        staged = R.stage_als_sorted(R.sort_als_triples(u, i, r), U + 5, I + 3)
        fit = data_parallel(
            R._als_fit_program(U + 5, I + 3, rank, 0.1, 2, False, 96),
            replicated_argnums=(6, 7))
        uf, itf, steps = map(np.asarray, fit(*staged, uf0, if0))
    assert int(steps) == 4
    assert not uf[U:].any() and not itf[I - 1:].any() and not itf[7].any()
    # the reference from the same init, by hand: its rule draws its own
    by_user = ref.Side(u, i, r, U)
    by_item = ref.Side(i, u, r, I)
    x, y = uf0[:U].astype(np.float64), if0[:I].astype(np.float64)
    for _ in range(2):
        x = by_user.solve(y, 0.1)
        y = by_item.solve(x, 0.1)
    assert np.abs(uf[:U] - x).max() < 1e-4
    assert np.abs(itf[:I] - y).max() < 1e-4


def test_the_compiled_program_holds_no_array_of_every_ratings_statistics():
    """At 2^20 ratings and rank 12 one array of ratings x statistics is
    377 MB (and the parent's rank^2 + rank columns 654 MB); the compiled
    program's temporaries are a block's, whatever the table's rows."""
    from sml_tpu.ml._staging import data_parallel
    rows, block, rank, U, I = 1 << 20, 1 << 14, 12, 4096, 2048
    width = R._stat_width(rank)
    shapes = [((rows,), jnp.int32), ((rows,), jnp.int32),
              ((rows,), jnp.float32), ((rows,), jnp.float32),
              ((1, 2, U), jnp.int32), ((1, 2, I), jnp.int32),
              ((U, rank), jnp.float32), ((I, rank), jnp.float32)]
    with meshlib.use_mesh(meshlib.build_mesh(1)):
        compiled = data_parallel(
            R._als_fit_program(U, I, rank, 0.1, 5, False, block),
            replicated_argnums=(6, 7)).lower(
                *[jax.ShapeDtypeStruct(s, d) for s, d in shapes]).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < rows * width * 4 / 8, temp
    # and a table of four times the rows asks for no more
    shapes[:4] = [((4 * rows,), d) for _, d in shapes[:4]]
    with meshlib.use_mesh(meshlib.build_mesh(1)):
        larger = data_parallel(
            R._als_fit_program(U, I, rank, 0.1, 5, False, block),
            replicated_argnums=(6, 7)).lower(
                *[jax.ShapeDtypeStruct(s, d) for s, d in shapes]).compile()
    # (the flags of the rows that begin a segment: a byte a row)
    assert larger.memory_analysis().temp_size_in_bytes < temp + 8 * 4 * rows
    import re
    for shape in re.findall(r"f32\[([0-9,]+)\]", compiled.as_text()):
        dims = [int(d) for d in shape.split(",")]
        assert not (rows in dims and int(np.prod(dims)) >= rows * rank), shape


def test_the_blocks_rows_follow_the_devices_memory(monkeypatch):
    """A quarter of the device for `_BLOCK_COPIES` arrays of a block's
    statistics, lanes padded: 2^21 rows on a v5e's 15.75 GiB at rank 12, a
    power of two always, and the v5e's where a device reports nothing."""
    class Device:
        def __init__(self, limit):
            self.limit = limit

        def memory_stats(self):
            return {"bytes_limit": self.limit} if self.limit else None

    def mesh_of(limit):
        return type("M", (), {"devices": np.array([Device(limit)],
                                                  dtype=object)})()
    for limit, want in ((16_911_433_728, 1 << 21), (None, 1 << 21),
                        (64 << 30, 1 << 23), (1 << 30, 1 << 17)):
        monkeypatch.setattr(meshlib, "get_mesh", lambda m=mesh_of(limit): m)
        assert R._block_rows(12) == want, limit
    # rank 16: 152 statistics, two lane tiles
    assert R._block_rows(16) == 1 << 16


# ------------------------------------------- the segment sums, alone
T = R._TILE


def _lengths(rows, rng):
    """Skewed segment lengths that fill `rows` rows: ones beside
    thousands."""
    out = []
    while rows:
        out.append(min(rows, int(rng.zipf(1.3)) % 3000 + 1))
        rows -= out[-1]
    return out


#: name -> (rows of the block, rows before the first flagged one: a
#: segment an earlier block began, the segments' lengths; None: skewed)
SEGMENTS = {
    "every_row_its_own": (1000, 0, [1] * 1000),
    "one_segment_the_whole_block": (2 * T * T + T, 0, [2 * T * T + T]),
    "one_segment_of_a_short_block": (96, 0, [96]),
    "begins_mid_segment": (T * T + 5 * T, T + 3, [3, T, T * T - 1, 7]),
    "begins_mid_segment_past_a_level": (2 * T * T, T * T + 1, [T * T - 1]),
    "ends_on_and_past_a_tiles_end": (
        6 * T, 0, [T, T + 1, T - 1, T, 1, 2 * T - 1]),
    "ends_on_and_past_a_second_level_tiles_end": (
        2 * T * T + 2 * T, 0, [T * T, T * T + 1, T - 1, T]),
    "ends_one_row_short_of_a_second_level_tile": (
        T * T + T, 0, [T * T - 1, 1, T]),
    "rows_64": (64, 0, None), "rows_96": (96, 0, None),
    "rows_1000": (1000, 0, None), "rows_16512": ((1 << 14) + 128, 0, None),
    "rows_64_mid_segment": (64, 9, None),
    "rows_1000_mid_segment": (1000, 130, None),
    "rows_16512_mid_segment": ((1 << 14) + 128, 300, None),
}


@pytest.mark.parametrize("name", list(SEGMENTS))
def test_the_segment_sums_are_a_float64_loops(name):
    """`_block_sums` (every entity's sum) and `_segment_sums` (every ROW's
    running sum, read as `_block_sums` reads it) against a float64 loop
    over the segments, rows of magnitudes four decades apart: the gap
    under 1e-6 of the segment's OWN magnitude sum, which a prefix that
    spans segments cannot hold (the controls below)."""
    rows, lead, lens = SEGMENTS[name]
    rng = np.random.default_rng(len(name) + rows)
    lens = lens or _lengths(rows - lead, rng)
    assert lead + sum(lens) <= rows
    s = lead + np.cumsum([0] + lens[:-1])
    t = s + np.asarray(lens)
    x = (rng.standard_normal((rows, 7))
         * np.exp(2 * rng.standard_normal((rows, 1)))).astype(np.float32)
    begins = np.zeros(rows, bool)
    begins[s] = True
    if lead:                      # the segment an earlier block began
        s, t = np.concatenate([[0], s]), np.concatenate([[lead], t])
    # two entities more with no row in the block, before and after
    s, t = (np.concatenate([[0], b, [rows]]) for b in (s, t))
    s[0] = t[0] = 0
    got = np.asarray(jax.jit(R._block_sums)(
        x, begins, s.astype(np.int32), t.astype(np.int32)))
    assert not got[0].any() and not got[-1].any()
    x64 = x.astype(np.float64)
    for a, b, row in zip(s[1:-1], t[1:-1], got[1:-1]):
        gap = np.abs(row - x64[a:b].sum(0)) / np.abs(x64[a:b]).sum(0)
        assert gap.max() < 1e-6, (name, a, b, gap.max())
    # every row: its running sum from its segment's first row
    run, carry = map(np.asarray, jax.jit(R._segment_sums)(x, begins))
    tile = min(T, rows)
    assert run.shape == (-(-rows // tile) * tile, 7)
    assert carry.shape == (-(-rows // tile), 7) and not carry[0].any()
    first = np.repeat(s[1:-1], t[1:-1] - s[1:-1])       # a row's segment's
    at = np.arange(first.size) + s[1]
    whole = run[at] + np.where((first < at // tile * tile)[:, None],
                               carry[at // tile], 0.0)
    want = np.cumsum(x64[s[1]:], axis=0)[:first.size]
    want -= np.concatenate([np.zeros((1, 7)), want])[first - s[1]]
    size = np.cumsum(np.abs(x64[s[1]:]), axis=0)[:first.size]
    size -= np.concatenate([np.zeros((1, 7)), size])[first - s[1]]
    assert (np.abs(whole - want) / size).max() < 1e-6, name


# ------------------------------------------------------------ the controls
@pytest.fixture(scope="module")
def tools():
    return runner.load_module(os.path.join(REPO, "benchmark", "tools_als.py"),
                              "bench_tools_als_t")


def _residual(model, pdf, reg=0.1):
    """The item side's float64 normal-equation residual at the model's
    factors, every movie."""
    uid, u = ref.dense(pdf["userId"].to_numpy())
    iid, i = ref.dense(pdf["movieId"].to_numpy())
    side = ref.Side(i, u, pdf["rating"].to_numpy(), len(iid))
    return ref.normal_residual(side, model._if, model._uf, reg,
                               np.arange(len(iid)))


def test_a_plain_float32_prefix_fails_the_residual_line(spark, tools, blocks):
    """One prefix over a block of 2^18 rows carries the block's whole sum
    into the difference that is a movie's one rating: the residual is past
    the cell's limit, and the sound program's far under it."""
    pdf = movielens.make({"rows": 250_000, "users": 2500, "items": 6000}, 4)
    blocks(1 << 18)
    df = spark.createDataFrame(pdf)
    est = ALS(rank=12, maxIter=2, regParam=0.1, seed=42, **COLS)
    with meshlib.use_mesh(meshlib.build_mesh(1)):
        sound = _residual(est.fit(df), pdf).max()
        with tools.plain_prefix():
            plain = _residual(est.fit(df), pdf).max()
    limit = _limits()["normal_residual_max"]
    assert sound < limit / 3 and plain > 3 * limit, (sound, plain)


def test_bfloat16_operands_fail_the_prediction_line(spark, tools, skewed):
    df = spark.createDataFrame(skewed)
    est = ALS(rank=12, maxIter=5, regParam=0.1, seed=42, **COLS)
    sound = _gaps(est.fit(df), skewed, 12, 5)[1]
    with tools.bfloat16_operands():
        lossy_model = est.fit(df)
    lossy = _gaps(lossy_model, skewed, 12, 5)[1]
    limit = _limits()["prediction_atol"]
    assert sound < limit / 3 and lossy > 3 * limit, (sound, lossy)
    assert _residual(lossy_model, skewed).max() > \
        3 * _limits()["normal_residual_max"]


def _limits():
    import json
    with open(os.path.join(REPO, "benchmark", "configs",
                           "mle01_als.json")) as f:
        return json.load(f)["correct"]


# ----------------------------------------------------------- the host path
def _ids(kind, n, rng):
    skew = (rng.zipf(1.3, n) % 50_000).astype(np.int64)
    return {"sparse_int64": skew * 3 + 7,
            "negative": skew - 20_000,
            "int32": (skew % 3000).astype(np.int32),
            "uint8": (skew % 200).astype(np.uint8),
            "wide": skew * 10**12,
            "one_value": np.full(n, 5, np.int64),
            "strings": np.array([f"u{v % 500}" for v in skew]),
            "floats": (skew % 700) / 2.0}[kind]


@pytest.mark.parametrize("rows", [1000, 200_000])
@pytest.mark.parametrize("kind", ["sparse_int64", "negative", "int32",
                                  "uint8", "wide", "one_value", "strings",
                                  "floats"])
def test_dense_ids_are_np_uniques_to_the_bit(kind, rows):
    """Under and over `_INLINE_ROWS` (inline, and by chunks on the pool);
    ids of a bounded range take the presence table, the others
    `np.unique` itself."""
    raw = _ids(kind, rows, np.random.default_rng(rows))
    ids, index = R.dense_ids(raw)
    want_ids, want_index = np.unique(raw, return_inverse=True)
    assert ids.dtype == want_ids.dtype and np.array_equal(ids, want_ids)
    assert index.dtype == np.int32 and np.array_equal(index, want_index)


@pytest.mark.parametrize("rows", [0, 1, 5000, 300_000])
def test_the_orders_and_bounds_are_the_stable_argsorts_to_the_bit(rows):
    rng = np.random.default_rng(rows + 1)
    u = (rng.zipf(1.2, rows) % 4000).astype(np.int32)
    i = (rng.zipf(1.5, rows) % 900).astype(np.int32)
    r = rng.random(rows).astype(np.float32)
    n_users = int(u.max()) + 1 if rows else 0
    order = R.stable_order(u, n_users)
    want = np.argsort(u, kind="stable")
    assert np.array_equal(order, want)
    starts, ends = R.segment_bounds(u, n_users)
    assert np.array_equal(starts, np.searchsorted(u[want], np.arange(n_users)))
    assert np.array_equal(ends, np.searchsorted(u[want],
                                                np.arange(n_users) + 1))
    if rows:
        prep = R.sort_als_triples(u, i, r)
        by_item = np.argsort(i, kind="stable")
        for name, held in (("ius", i[want]), ("rat_u", r[want]),
                           ("usi", u[by_item]), ("rat_i", r[by_item])):
            assert prep[name].dtype == held.dtype
            assert np.array_equal(prep[name], held), name


def test_the_stable_order_holds_under_more_workers_than_cores(monkeypatch):
    """Chunks of a few thousand rows, forty of them on the pool at once
    under a shortened switch interval: every chunk writes its own places
    of the one order, so no interleaving may move a row."""
    from sml_tpu.ml import _column_plan
    monkeypatch.setattr(_column_plan, "_cores", lambda: 40)
    rng = np.random.default_rng(0)
    u = (rng.zipf(1.2, 150_000) % 3000).astype(np.int32)
    held = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for _ in range(5):
            assert np.array_equal(R.stable_order(u, 3000),
                                  np.argsort(u, kind="stable"))
    finally:
        sys.setswitchinterval(held)


# ------------------------------------------------ spans, counters, the drop
def test_a_fit_opens_the_standard_children_and_counts(spark, skewed, blocks):
    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    blocks(1000)
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    try:
        train = spark.createDataFrame(skewed.iloc[::2])
        rest = spark.createDataFrame(skewed.iloc[1::2])
        with meshlib.use_mesh(meshlib.build_mesh(1)):
            model = ALS(rank=4, maxIter=3, seed=1, coldStartStrategy="drop",
                        **COLS).fit(train)
        got = obs.RECORDER.counters()
        for name in ("fit", "fit.collect", "fit.featurize",
                     "fit.featurize.als.index", "fit.featurize.als.sort",
                     "program.als_fit", "fit.stage", "fit.dispatch",
                     "fit.device_wait", "fit.readback"):
            assert got["span_n." + name] == 1, name
        inside = sum(got["span_s." + n] for n in (
            "fit.collect", "fit.featurize", "program.als_fit"))
        assert inside <= got["span_s.fit"]
        assert got["span_s.fit.featurize.als.index"] \
            + got["span_s.fit.featurize.als.sort"] \
            <= got["span_s.fit.featurize"]
        assert got["als.fits"] == 1 and got["als.half_steps"] == 6
        assert got["als.ratings"] == len(skewed.iloc[::2])
        padded = meshlib.bucket_rows(len(skewed.iloc[::2]), 1)
        assert got["als.blocks"] == -(-padded // 1000)
        # the cold start: rows whose user or movie the half lacks
        served = model.transform(rest).toPandas()
        after = obs.RECORDER.counters()
        known = skewed.iloc[1::2]["movieId"].isin(model._item_ids) \
            & skewed.iloc[1::2]["userId"].isin(model._user_ids)
        assert (~known).sum() > 0
        assert after["als.cold_start.dropped"] == (~known).sum()
        assert len(served) == known.sum()
        assert after["span_n.transform.als.lookup"] >= 1
    finally:
        GLOBAL_CONF.set("sml.obs.enabled", False)
        obs.reset()
