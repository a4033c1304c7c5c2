"""A count the run took (from the program's counters or the allocator),
times a scale."""


def read(run, params):
    v = run["spans"].counters.get(params["counter"])
    return None if v is None else v * params.get("scale", 1.0)
