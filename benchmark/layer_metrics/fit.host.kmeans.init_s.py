"""Seconds a timed fit spends in the program's span `kmeans.init.local`:
what of the seeding runs on the HOST (the draws' key; the rows of
`initMode="random"`). The rounds of k-means|| and the weighted k-means++
are inside the fit's one dispatch (`fit.device.kmeans.init_s`), so this
reads about 0; a program that seeds on the host shows it here."""

from benchmark.layer_metrics import _fit_spans


def read(run):
    if "span_n.kmeans.init.local" not in run.counters_end:
        return None
    return _fit_spans.seconds_per_fit(run, ("kmeans.init.local",))
