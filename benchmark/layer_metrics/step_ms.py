"""``step_ms`` (layer: step): the resident loop's seconds over its steps:
what one train step costs the device when nothing has to be fed."""


def read(facts):
    if not facts.get("resident_step_s"):
        return None
    return facts["resident_step_s"] * 1e3
