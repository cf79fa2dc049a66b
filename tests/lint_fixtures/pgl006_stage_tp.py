"""PGL006 true positives, stage names. Expected findings: 2."""


def unbounded_stage(telemetry, rid):
    with telemetry.stage(f"serve/decode/{rid}"):  # TP: f-string stage name
        pass


def computed_stage(stage, names, i):
    with stage(names[i]):  # TP: non-literal expression
        pass
