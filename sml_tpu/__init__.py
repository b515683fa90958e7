"""sml_tpu — a TPU-native scalable-ML framework.

A from-scratch re-design of the capabilities exercised by the reference
courseware (Databricks "Scalable Machine Learning with Apache Spark" 3.7.3):
a partitioned DataFrame engine, Delta-lite versioned storage, an
MLlib-compatible pipeline/estimator API whose distributed math runs as jitted
XLA programs over a `jax.sharding.Mesh` with ICI collectives, tree/GBT
histogram learners, tuning (grid CV + TPE), a pandas function API, and
MLOps glue (tracking/registry/feature store/AutoML) — single-process Python
driver, no JVM, native C++ for host-side hot ops.
"""

import os as _os
import time as _time


def _process_age_s():
    """Seconds since the process started (`/proc/self/stat`'s start time
    against the clock it is kept on, CLOCK_BOOTTIME); None where there is
    no `/proc`."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return _time.clock_gettime(_time.CLOCK_BOOTTIME) \
            - ticks / _os.sysconf("SC_CLK_TCK")
    except (OSError, AttributeError, ValueError, IndexError):
        return None


# two facts of the process that only the package can know, published once
# the import is done (the recorder's gauges `process.age_at_import_s` and
# `process.import_s`): a set-up's seconds before the program's first span
_age_at_import_s = _process_age_s()
_import_t0 = _time.monotonic()


def _require_pandas_cow() -> None:
    """The frame layer's shallow-copy memoization (`toPandas` caching,
    `pdf.copy(deep=False)` views) is only mutation-safe under pandas
    copy-on-write, which pandas>=3 has always on; anything older is
    refused (an in-place mutation of a returned frame must never corrupt
    a cached parent)."""
    import pandas as pd
    if int(pd.__version__.split(".")[0]) < 3:
        raise ImportError(
            f"sml_tpu requires pandas>=3 (found {pd.__version__})")


_require_pandas_cow()

# XLA's persistent compilation cache: a fresh process reuses every program
# compiled by an earlier one. Placed by `JAX_COMPILATION_CACHE_DIR` when
# set, else `sml.compile.cacheDir`, else `<checkout>/.jax_cache`
# (`parallel.dispatch.ensure_compile_cache`). A failure to set it up fails
# the import: a run that silently recompiles everything is not the run
# that was asked for.
from .parallel.dispatch import ensure_compile_cache as _ensure_compile_cache

_ensure_compile_cache()

from .conf import GLOBAL_CONF
from .frame import DataFrame, Row, TpuSession, functions, get_session
from .version import __version__


def _note_process() -> None:
    from .obs._recorder import RECORDER
    facts = {"import_s": _time.monotonic() - _import_t0}
    if _age_at_import_s is not None:
        facts["age_at_import_s"] = _age_at_import_s
    RECORDER.note_process(**facts)


_note_process()


def install_shims() -> None:
    """Register the pyspark/mlflow/hyperopt/databricks import shims so
    reference course code runs unchanged (see sml_tpu/compat.py)."""
    from .compat import install_shims as _install
    _install()


__all__ = ["TpuSession", "DataFrame", "Row", "functions", "get_session",
           "GLOBAL_CONF", "install_shims", "__version__"]
