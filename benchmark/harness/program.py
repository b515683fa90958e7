"""Everything the benchmark takes from the program under test (`sml_tpu`):
the entry points a user calls, and the program's own counters, dispatch
audit rows and spans. The harness's arithmetic and the references import
nothing from here, and this module computes no metric.
"""

from __future__ import annotations

import importlib
import time
from typing import Dict, List, Tuple

import numpy as np

#: counters that must not move in a run: each one is a path that hides
#: the device or the compiled kernel
ZERO_COUNTERS = ("serve.host_routed", "kernel.fallback",
                 "infer.kernel.fallback", "native.build_failed")


def configure(conf: Dict[str, object]) -> str:
    """Set the configuration's `sml.*` keys and turn the flight recorder on
    (counters and audit rows are read in every run). Returns the
    compile-cache directory."""
    from sml_tpu import obs
    from sml_tpu.conf import GLOBAL_CONF
    from sml_tpu.parallel import dispatch
    for key, value in conf.items():
        GLOBAL_CONF.set(key, value)
    GLOBAL_CONF.set("sml.obs.enabled", True)
    obs.reset()
    return dispatch.ensure_compile_cache()


def make_table(rows):
    """The configuration's table (a pandas frame made from the seed by the
    generator the configuration names) as a cached DataFrame."""
    from sml_tpu.frame.session import get_session
    df = get_session().createDataFrame(rows)
    df.cache()
    return df


def with_label(config: Dict, df):
    """The frame the estimator is fitted on: where the configuration's
    label names a `function` (any unary one of the frame's functions, by
    name), `fit_column` is that function of `column` (ML 11 fits
    log(price)); where it names none, the frame as it is."""
    label = config["label"]
    if label.get("function"):
        from sml_tpu.frame import functions as F
        fn = getattr(F, label["function"])
        return df.withColumn(label["fit_column"], fn(F.col(label["column"])))
    return df


def split(df, fractions: List[float], seed: int):
    parts = df.randomSplit([float(f) for f in fractions], seed=int(seed))
    for p in parts:
        p.cache()
    return parts


def build_pipeline(config: Dict):
    """The configuration's `pipeline`: its stages in order, each a class
    of the program named by module and class, with its parameters."""
    from sml_tpu.ml import Pipeline
    stages = []
    for stage in config["pipeline"]:
        cls = getattr(importlib.import_module(stage["module"]),
                      stage["class"])
        stages.append(cls(**stage["params"]))
    return Pipeline(stages=stages)


def model_tables(model) -> Dict[str, object]:
    """The fitted pipeline as plain arrays: what the references descend.
    Imputer surrogates, indexer labels, bin edges, category ranks and the
    node tables are the MODEL; everything computed from them is not taken
    from the program. The stages are found by what they hold, not by their
    place: an imputer, an indexer, the assembler that orders their outputs
    and a tree ensemble (what `kinds/fit.py` checks; another family of
    model brings its own kind and its own tables)."""
    def stage(attr):
        return next(s for s in model.stages if hasattr(s, attr))
    imputer, indexer = stage("surrogates"), stage("labelsArray")
    assembler = next(s for s in model.stages
                     if s.hasParam("inputCols") and s.hasParam("outputCol")
                     and not hasattr(s, "labelsArray"))
    spec = stage("_spec")._spec
    sf, sb, lv, w = spec.stacked()
    numeric = list(imputer.getOrDefault("inputCols"))
    categorical = list(indexer.getOrDefault("inputCols"))
    made = dict(zip(imputer.getOrDefault("outputCols"),
                    (("numeric", c) for c in numeric)))
    made.update(zip(indexer.getOrDefault("outputCols"),
                    (("categorical", c) for c in categorical)))
    return {
        "columns": [made[c] for c in assembler.getOrDefault("inputCols")],
        "surrogates": {c: float(imputer.surrogates[c]) for c in numeric},
        "labels": {c: list(ls)
                   for c, ls in zip(categorical, indexer.labelsArray)},
        "edges": np.asarray(spec.binning.edges, dtype=np.float32),
        "cat_rank": {int(k): np.asarray(v, dtype=np.int64)
                     for k, v in spec.binning.cat_remap.items()},
        "split_feature": np.asarray(sf, dtype=np.int64),
        "split_bin": np.asarray(sb, dtype=np.int64),
        "leaf_value": np.asarray(lv, dtype=np.float32),
        "tree_weight": np.asarray(w, dtype=np.float32),
        "cover": np.stack([np.asarray(t.cover) for t in spec.trees]),
        "base": float(spec.base),
        "depth": int(spec.depth),
    }


def predictions(model, df) -> np.ndarray:
    """`model.transform(df)` with the predictions brought to the host."""
    out = model.transform(df).select("prediction").toPandas()
    return np.asarray(out["prediction"], dtype=np.float64)


def counters() -> Dict[str, float]:
    from sml_tpu import obs
    return dict(obs.RECORDER.counters())


def routes() -> Dict[str, int]:
    """Audited dispatches by route."""
    from sml_tpu import obs
    out: Dict[str, int] = {}
    for r in obs.audit_records():
        out[r.route] = out.get(r.route, 0) + 1
    return out


def recorder_spans() -> List[Tuple[str, float, float]]:
    """The flight recorder's span events as (name, start, end) on the
    `time.perf_counter` clock (the ring keeps the newest 65,536 events)."""
    from sml_tpu import obs
    rec = obs.RECORDER
    offset = rec.epoch_unix() - (time.time() - time.perf_counter())
    return [(e.name, e.ts + offset, e.ts + offset + (e.dur or 0.0))
            for e in rec.events() if e.kind == "span" and e.dur]
