"""Process start to the first timed request: data from the seed, the
service, the warm-up requests and every compilation they trigger."""


def read(run):
    return run.setup_s
