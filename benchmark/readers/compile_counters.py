"""A count or a sum of seconds from the program's compile counters
(``progen_tpu.telemetry.compiles``, fed by ``jax.monitoring``), over
set-up: everything before the window opened. The spec names the
``event`` (backend_compile or cache_misses) and the ``field`` (count or
seconds). A program without the counters, or one in which they were
never installed (``load_env_file()`` does it), gives nothing to read."""


def read(run, spec):
    try:
        from progen_tpu.telemetry import compiles
    except ImportError:
        return None
    if run.t_open is None or not compiles.installed():
        return None
    return compiles.snapshot(until=run.t_open)[spec["event"]][spec["field"]]
