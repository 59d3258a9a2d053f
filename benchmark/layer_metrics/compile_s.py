"""``compile_s``: seconds jax spent compiling (or loading from the
persistent cache) during set-up, summed over the programs (CompileMeter)."""


def read(run: dict):
    return run["setup"]["compile_s"]
