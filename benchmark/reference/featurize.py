"""Raw rows -> the (n, F) feature block, as the fitted pipeline defines it:
one column for each input of its assembler, in the assembler's order, a
StringIndexer index or an imputed numeric, in float32."""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from . import forest


def featurize(rows, tables: Dict) -> np.ndarray:
    """`rows` is a pandas frame of raw columns. Indices come from the fitted
    indexer's label lists (position = index), missing numerics take the
    fitted imputer's surrogate. A label the indexer never saw is an error:
    the traffic is made from the table the model was fitted on."""
    cols = []
    for kind, name in tables["columns"]:
        if kind == "categorical":
            index = {lab: float(i)
                     for i, lab in enumerate(tables["labels"][name])}
            col = rows[name].map(index)
            if col.isna().any():
                raise ValueError(f"column {name} holds a label the model's "
                                 f"indexer has not seen")
            cols.append(col.to_numpy(dtype=np.float64))
        else:
            col = rows[name].to_numpy(dtype=np.float64)
            cols.append(np.where(np.isnan(col), tables["surrogates"][name],
                                 col))
    return np.stack(cols, axis=1).astype(np.float32)


def bins(rows, tables: Dict, missing: Optional[float] = None) -> np.ndarray:
    """Raw rows -> their bin indices under the fitted edges and ranks;
    `missing` is the feature value the configuration's estimator treats
    as absent (`fit_math.missing`), if it names one."""
    return forest.bin_features(featurize(rows, tables), tables["edges"],
                               tables["cat_rank"], missing)
