"""Share of the chip's HBM bandwidth that the USEFUL traffic of the build
of the normal equations is, over its device time: `als.half_steps` x
(ratings x (8 + 4 x rank) bytes read + entities x (rank^2 + rank) x 4
written) a fit (`_als_work.build_bytes`) over (`fit.device.als.gather_s` +
`fit.device.als.normal_s`) x the peak keyed by `device_kind`. It counts
the same work whatever implements it: a scan's passes over a block's
statistics are no useful byte."""

from benchmark.layer_metrics import _als_scopes, _als_work


def read(run):
    # a compiler that fuses the gather into the statistics files it there
    gather = _als_scopes.seconds_per_fit(run, "als.gather") or 0.0
    normal = _als_scopes.seconds_per_fit(run, "als.normal")
    fits, rank = run.facts.get("fits"), run.facts.get("als_rank")
    entities = run.facts.get("als_entities")
    if not normal or not fits or not rank or not entities \
            or not run.counter_delta("als.half_steps"):
        return None
    work = _als_work.build_bytes(
        sum(run.facts["fit_rows"]) / fits, entities, rank,
        run.counter_delta("als.half_steps") / fits)
    return 100.0 * work / ((gather + normal) * _als_work.peak_bytes_per_s(
        run.device["kind"]))
