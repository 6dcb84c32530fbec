"""Distance evaluations per dispatched query slot, padding included (the
program's ``SearchResult.n_dists`` summed by the engine)."""


def read(r):
    d = r.layer.get("dists_per_query")
    return None if not d else float(d)
