"""Set-up of the first run against this compile cache minus this run's:
what compiling cost. The first run itself has nothing to compare."""


def read(run, spec):
    first = run.counters.get("first_setup_s")
    return None if first is None else first - run.counters["setup_s"]
