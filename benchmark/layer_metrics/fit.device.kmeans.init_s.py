"""Device seconds a timed fit under the program's scope `kmeans.init`: the
k-means|| seeding inside the fit's one dispatch (the rounds' blocked passes
against the new candidates, the draws, the candidates' weights, the
weighted k-means++ and its Lloyd steps over the candidates)."""

from benchmark.layer_metrics import _kmeans_scopes


def read(run):
    return _kmeans_scopes.seconds_per_fit(run, "kmeans.init")
