"""Per-layer metric `setup_lower_s`: see `bench.program_obs.setup_lower_s`."""
from bench import program_obs


def read(ctx):
    return program_obs.setup_lower_s(ctx)
