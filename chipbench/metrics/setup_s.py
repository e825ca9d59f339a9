"""Process start to window start: imports, server build, compile or cache
load, warm-up and the generator's connections."""


def read(run):
    return run["setup_s"]
