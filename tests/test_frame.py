import numpy as np
import pandas as pd
import pytest

import sml_tpu.frame.functions as F


def test_range_and_count(spark):
    df = spark.range(1000)
    assert df.count() == 1000
    assert df.columns == ["id"]
    assert df.rdd.getNumPartitions() >= 1


def test_select_withcolumn_filter(spark):
    df = spark.range(100)
    out = (df.withColumn("x", F.col("id") * 2)
             .withColumn("y", F.col("x") + 1)
             .filter(F.col("id") < 10)
             .select("id", "y"))
    pdf = out.toPandas()
    assert len(pdf) == 10
    assert list(pdf["y"]) == [i * 2 + 1 for i in range(10)]


def test_when_otherwise_translate_cast(spark):
    pdf = pd.DataFrame({"price": ["$1,200.00", "$85.00", "$3.50"]})
    df = spark.createDataFrame(pdf)
    out = df.withColumn("price_d", F.translate(F.col("price"), "$,", "").cast("double"))
    vals = out.toPandas()["price_d"].tolist()
    assert vals == [1200.0, 85.0, 3.5]

    df2 = spark.createDataFrame(pd.DataFrame({"n": [1.0, 5.0, 10.0]}))
    out2 = df2.withColumn("cls", F.when(F.col("n") > 6, "high")
                          .when(F.col("n") > 2, "mid").otherwise("low"))
    assert out2.toPandas()["cls"].tolist() == ["low", "mid", "high"]


def test_groupby_agg(spark):
    pdf = pd.DataFrame({"k": ["a", "b", "a", "b", "a"], "v": [1.0, 2.0, 3.0, 4.0, 5.0]})
    df = spark.createDataFrame(pdf)
    out = df.groupBy("k").agg(F.avg("v").alias("m"), F.count("*").alias("c")).orderBy("k")
    res = out.toPandas()
    assert res["m"].tolist() == [3.0, 3.0]
    assert res["c"].tolist() == [3, 2]


def test_groupby_count(spark, airbnb_df):
    out = airbnb_df.groupBy("room_type").count().orderBy(F.col("count").desc())
    res = out.toPandas()
    assert res["count"].sum() == 2000
    assert res["count"].iloc[0] >= res["count"].iloc[-1]


def test_orderby_limit(spark, airbnb_df):
    top = airbnb_df.orderBy(F.col("price").desc()).limit(5).toPandas()
    all_prices = airbnb_df.toPandas()["price"]
    assert top["price"].iloc[0] == all_prices.max()
    assert len(top) == 5


def test_random_split_seeded_deterministic(spark, airbnb_df):
    a1, b1 = airbnb_df.randomSplit([0.8, 0.2], seed=42)
    a2, b2 = airbnb_df.randomSplit([0.8, 0.2], seed=42)
    assert a1.count() == a2.count()
    assert b1.count() == b2.count()
    assert a1.count() + b1.count() == 2000
    # roughly 80/20
    assert 0.7 < a1.count() / 2000 < 0.9


def test_random_split_partition_dependence(spark, airbnb_pdf):
    """The ML 02:38-52 lesson: same seed, different partition layout ⇒
    different membership."""
    df8 = spark.createDataFrame(airbnb_pdf, numPartitions=8)
    df2 = spark.createDataFrame(airbnb_pdf, numPartitions=2)
    a8, _ = df8.randomSplit([0.8, 0.2], seed=42)
    a2, _ = df2.randomSplit([0.8, 0.2], seed=42)
    ids8 = set(a8.toPandas()["id"])
    ids2 = set(a2.toPandas()["id"])
    assert ids8 != ids2  # partition-dependent, as documented


def test_dropduplicates_union_join(spark):
    pdf = pd.DataFrame({"k": [1, 2, 2, 3], "v": ["a", "b", "b", "c"]})
    df = spark.createDataFrame(pdf)
    assert df.dropDuplicates().count() == 3
    assert df.union(df).count() == 8
    right = spark.createDataFrame(pd.DataFrame({"k": [1, 2], "w": [10.0, 20.0]}))
    j = df.dropDuplicates().join(right, on="k", how="inner").orderBy("k").toPandas()
    assert j["w"].tolist() == [10.0, 20.0]
    anti = df.dropDuplicates().join(right, on="k", how="left_anti").toPandas()
    assert anti["k"].tolist() == [3]


def test_describe_summary_quantile(spark, airbnb_df):
    d = airbnb_df.describe("price").toPandas()
    assert d["summary"].tolist() == ["count", "mean", "stddev", "min", "max"]
    assert float(d["price"][0]) == 2000
    s = airbnb_df.select("price").summary().toPandas()
    assert "50%" in s["summary"].tolist()
    q = airbnb_df.approxQuantile("price", [0.5], 0.01)
    assert q[0] > 0


def test_repartition_coalesce(spark):
    df = spark.range(100)
    assert df.repartition(10).rdd.getNumPartitions() == 10
    assert df.repartition(10).coalesce(3).rdd.getNumPartitions() == 3
    assert df.repartition(10).count() == 100
    byk = df.withColumn("k", F.col("id") % 4).repartition(4, "k")
    assert byk.count() == 100


def test_monotonic_id_and_partition_id(spark):
    df = spark.range(100, numPartitions=4).withColumn("mid", F.monotonically_increasing_id())
    pdf = df.toPandas()
    assert pdf["mid"].is_unique
    pids = spark.range(100, numPartitions=4).select(F.spark_partition_id().alias("p")).toPandas()
    assert set(pids["p"]) == {0, 1, 2, 3}


def test_rand_seeded(spark):
    df = spark.range(50, numPartitions=2)
    a = df.withColumn("r", F.rand(seed=1)).toPandas()["r"]
    b = df.withColumn("r", F.rand(seed=1)).toPandas()["r"]
    assert np.allclose(a, b)
    assert a.between(0, 1).all()


def test_temp_view_sql(spark, airbnb_df):
    airbnb_df.createOrReplaceTempView("listings")
    out = spark.sql("SELECT room_type, COUNT(*) AS n FROM listings GROUP BY room_type ORDER BY n DESC")
    pdf = out.toPandas()
    assert pdf["n"].sum() == 2000


def test_filter_string_expr(spark, airbnb_df):
    assert airbnb_df.filter("bedrooms >= 2 AND price > 100").count() > 0


def test_na_functions(spark):
    pdf = pd.DataFrame({"a": [1.0, None, 3.0], "b": ["x", "y", None]})
    df = spark.createDataFrame(pdf)
    assert df.na.drop().count() == 1
    assert df.na.drop(subset=["a"]).count() == 2
    filled = df.na.fill(0.0).toPandas()
    assert filled["a"].tolist() == [1.0, 0.0, 3.0]


def test_cache_and_lazy(spark):
    df = spark.range(10).withColumn("x", F.col("id") + 1)
    assert df._parts is None  # lazy until an action
    df.cache()
    assert df._parts is not None


def test_collect_rows(spark):
    rows = spark.range(3).collect()
    assert [r.id for r in rows] == [0, 1, 2]
    assert rows[0]["id"] == 0
    assert rows[0].asDict() == {"id": 0}


def test_csv_roundtrip(spark, airbnb_pdf, tmp_path):
    p = str(tmp_path / "listings_csv")
    spark.createDataFrame(airbnb_pdf).write.option("header", True).csv(p)
    back = spark.read.csv(p, header=True, inferSchema=True)
    assert back.count() == 2000
    assert "price" in back.columns


def test_parquet_roundtrip_partitions(spark, airbnb_pdf, tmp_path):
    p = str(tmp_path / "listings_pq")
    spark.createDataFrame(airbnb_pdf, numPartitions=8).write.mode("overwrite").parquet(p)
    back = spark.read.parquet(p)
    assert back.count() == 2000
    assert back.rdd.getNumPartitions() == 8  # one part-file per partition


def test_null_group_key(spark):
    pdf = pd.DataFrame({"k": ["a", None, "a"], "v": [1.0, 2.0, 3.0]})
    out = spark.createDataFrame(pdf).groupBy("k").agg(F.sum("v").alias("s")).toPandas()
    assert len(out) == 2 and out["s"].sum() == 6.0


def test_union_positional(spark):
    a = spark.createDataFrame(pd.DataFrame({"x": [1]}))
    b = spark.createDataFrame(pd.DataFrame({"y": [2]}))
    assert a.union(b).toPandas()["x"].tolist() == [1, 2]


def test_case_when_null_then_value(spark):
    pdf = pd.DataFrame({"a": [1.0, -1.0], "b": [None, None]})
    out = spark.createDataFrame(pdf).withColumn(
        "c", F.when(F.col("a") > 0, F.col("b")).otherwise(F.lit("OTH"))).toPandas()
    assert out["c"].tolist() == [None, "OTH"]


def test_boolean_cast_strings(spark):
    pdf = pd.DataFrame({"s": ["true", "false", "junk"]})
    out = spark.createDataFrame(pdf).withColumn("b", F.col("s").cast("boolean")).toPandas()
    assert out["b"].tolist() == [True, False, None]


def test_head_empty(spark):
    assert spark.createDataFrame(pd.DataFrame({"a": []})).head() is None


def test_partitioned_append(spark, tmp_path):
    p = str(tmp_path / "papp")
    spark.createDataFrame(pd.DataFrame({"k": [1], "v": [1.0]})) \
        .write.partitionBy("k").mode("overwrite").parquet(p)
    spark.createDataFrame(pd.DataFrame({"k": [1], "v": [2.0]})) \
        .write.partitionBy("k").mode("append").parquet(p)
    assert spark.read.parquet(p).count() == 2


def test_sql_view_materialization_is_cached(spark, airbnb_pdf, monkeypatch):
    """Repeated SQL over the same view loads it into the session store ONCE;
    re-registering the view invalidates (VERDICT r2 weak #7)."""
    from sml_tpu.frame import sql as sqlmod
    calls = []
    orig = sqlmod._to_sqlite

    def counting(pdf, name, con):
        calls.append(name)
        return orig(pdf, name, con)

    monkeypatch.setattr(sqlmod, "_to_sqlite", counting)
    df = spark.createDataFrame(airbnb_pdf)
    df.createOrReplaceTempView("cached_view")
    n1 = spark.sql("SELECT count(*) AS n FROM cached_view").toPandas()
    n2 = spark.sql("SELECT avg(price) AS p FROM cached_view").toPandas()
    assert calls.count("cached_view") == 1  # one load serves both queries
    assert int(n1["n"].iloc[0]) == len(airbnb_pdf)
    # replacing the view re-materializes
    df2 = spark.createDataFrame(airbnb_pdf.iloc[:100])
    df2.createOrReplaceTempView("cached_view")
    n3 = spark.sql("SELECT count(*) AS n FROM cached_view").toPandas()
    assert int(n3["n"].iloc[0]) == 100
    assert calls.count("cached_view") == 2


def test_sql_dropped_view_errors_not_stale(spark, airbnb_pdf):
    """Dropping a view must invalidate the session SQL store — a query on
    the dropped name errors instead of returning the stale copy."""
    import pandas.errors
    df = spark.createDataFrame(airbnb_pdf)
    df.createOrReplaceTempView("doomed_view")
    assert spark.sql("SELECT count(*) n FROM doomed_view").toPandas() is not None
    spark.catalog.dropTempView("doomed_view")
    with pytest.raises((pandas.errors.DatabaseError, Exception)):
        spark.sql("SELECT count(*) n FROM doomed_view").toPandas()


def test_tail(spark):
    df = spark.createDataFrame(pd.DataFrame({"x": list(range(10))}))
    rows = df.tail(3)
    assert [r["x"] for r in rows] == [7, 8, 9]
    assert len(df.tail(99)) == 10


def test_shuffle_reuse_cache_and_unpersist(spark):
    """applyInPandas memoizes the group split of a cached frame; a
    mutating fn cannot pollute it; unpersist drops the entries; the byte
    bound refuses oversized splits."""
    import pandas as pd
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.frame import grouped as G

    pdf = pd.DataFrame({"k": ["a", "b", "c"] * 400,
                        "v": np.arange(1200, dtype=float)})
    df = spark.createDataFrame(pdf)
    df.cache()
    df.toPandas()

    def fn(g):
        g["v"] = -1.0  # hostile in-place mutation
        return pd.DataFrame({"k": [g["k"].iloc[0]], "n": [len(g)]})

    sch = "k string, n bigint"
    r1 = df.groupby("k").applyInPandas(fn, sch).toPandas()
    with G._split_lock:
        assert any(v[0] is df.__dict__["_pdf_cache"]
                   for v in G._split_cache.values())
    r2 = df.groupby("k").applyInPandas(fn, sch).toPandas()
    assert sorted(r1["n"]) == sorted(r2["n"]) == [400, 400, 400]
    assert float(df.toPandas()["v"].min()) >= 0  # source unpolluted
    token = df.__dict__["_pdf_cache"]
    df.unpersist()
    with G._split_lock:
        assert not any(v[0] is token for v in G._split_cache.values())

    # byte bound: a 0 budget refuses to cache at all
    old = GLOBAL_CONF.get("sml.shuffle.reuseBytes")
    GLOBAL_CONF.set("sml.shuffle.reuseBytes", 0)
    try:
        df2 = spark.createDataFrame(pdf)
        df2.cache()
        df2.toPandas()
        df2.groupby("k").applyInPandas(fn, sch).toPandas()
        tok2 = df2.__dict__["_pdf_cache"]
        with G._split_lock:
            assert not any(v[0] is tok2 for v in G._split_cache.values())
    finally:
        GLOBAL_CONF.set("sml.shuffle.reuseBytes", old)


def test_a_string_columns_type_is_read_from_its_storage_not_its_values():
    """`infer_schema_from_pandas` called a Python function a value to tell
    strings from lists: 2.3 s for five string columns of 1.6 M rows, at
    every `df.schema` of a new frame. String storage cannot hold a list."""
    import pandas as pd
    from sml_tpu.frame import types as T
    pdf = pd.DataFrame({
        "arrow": pd.array(["a", None, "c"], dtype="string[pyarrow]"),
        "python": pd.array(["a", None, "c"], dtype="string[python]"),
        "object": pd.Series(["a", None, "c"], dtype=object),
        "lists": pd.Series([[1.0], [2.0], [3.0]], dtype=object),
        "x": [1.0, 2.0, 3.0]})
    called = []
    real = pd.Series.map
    try:
        pd.Series.map = lambda self, *a, **k: (called.append(self.name),
                                               real(self, *a, **k))[1]
        sch = T.infer_schema_from_pandas(pdf)
    finally:
        pd.Series.map = real
    assert [f.dataType.simpleString() for f in sch.fields] == [
        "string", "string", "string", "vector", "double"]
    assert called == ["object", "lists"]
