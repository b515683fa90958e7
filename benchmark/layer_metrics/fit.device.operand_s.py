"""Device seconds a timed fit under the program's scope `tree.operand` (own
time of the operations inside `bench.fit`): building the operand of the
histogram dots: the bins widened to s32, the s32 broadcast they are compared
in and the one-hot `B1t` it yields."""

from benchmark.layer_metrics import _fit_scopes


def read(run):
    return _fit_scopes.seconds_per_fit(run, "tree.operand")
