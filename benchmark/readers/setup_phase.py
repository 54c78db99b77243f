"""One phase of the set-up, by the harness's clock, in seconds."""


def read(run, params):
    return run.setup.get(params["phase"])
