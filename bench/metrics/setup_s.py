"""Process start to the first timed request: network build or artifact
load, deploy, compiles and warm-up."""


def read(run):
    return run.setup_s
