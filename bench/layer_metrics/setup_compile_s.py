"""Per-layer metric `setup_compile_s`: see
`bench.program_obs.setup_compile_s`."""
from bench import program_obs


def read(ctx):
    return program_obs.setup_compile_s(ctx)
