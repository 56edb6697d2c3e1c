"""optimal_share: share (%) of the window's stochastic-search jobs whose
delivered objective equals the reference's pruned-space optimum of the
same job (a missing answer is not optimal)."""


def read(run):
    if run.method == "exhaustive" or not run.optimal:
        return None
    return 100.0 * sum(run.optimal) / len(run.optimal)
