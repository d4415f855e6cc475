"""Host time of ``.lower().compile()`` of the trainer's own step: a
compile on a cold cache, a load from the persistent cache on a warm
one."""


def read(rec):
    return rec.get("compile_s")
