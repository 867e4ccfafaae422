"""Process start to the first timed request: build check, verifyd spawn to
``ready``, cluster and gateway up, the ramp."""


def reduce(run: dict, args: dict):
    return run["setup_s"]
