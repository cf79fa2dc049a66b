"""PGL006 true negatives, stage names: a literal name; a wrapper
forwarding its own parameter."""


def literal_stage(telemetry):
    with telemetry.stage("serve/admit"):
        pass


def forwarding_wrapper(telemetry, name):
    return telemetry.stage(name)
