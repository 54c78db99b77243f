"""Mean over the window's requests of a count the response carries."""


def read(run, params):
    values = [getattr(r, params["field"]) for r in run.records]
    return sum(values) / len(values) if values else None
