"""Device time of the step's collectives (``all-gather*``,
``all-reduce*`` and the like in the traced steps), per step and per
chip. Only cells on several chips have any."""


def read(rec):
    trace = rec.get("trace")
    if not trace or rec.get("chips", 1) < 2 or not trace["step_programs"]:
        return None
    return 1e3 * trace["collective_s"] / trace["step_programs"]
