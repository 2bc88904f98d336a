"""Process start to the first timed request: synthesis, registration,
weights and features, compilation or cache loads, warm-up."""


def read(run):
    return run.setup_s
