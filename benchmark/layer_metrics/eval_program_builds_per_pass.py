"""``eval_program_builds_per_pass``: executables the window's passes built or
dropped (the deltas of ``compiles`` + ``evictions`` of
``ShapeCachedForward.stats``, which the driver ``eval_pass_kitti`` puts in
its report) over the window's passes. 0 on a sound run: every native size
has its program before the window and the cache holds them all; a pass that
cuts its groups by run, or a cache smaller than the set of sizes, reads
several a pass (and the run is not ``correct``: ``window_program_builds``).
``None`` where the driver hands no report."""


def read(run: dict):
    report = run["report"]
    built, passes = report.get("executables"), report.get("passes")
    if built is None or not passes:
        return None
    return (built["compiles"] + built["evictions"]) / passes
